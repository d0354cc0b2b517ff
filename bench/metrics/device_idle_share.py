"""Share of the traced window in which no operation ran on the device:
1 - (union of device busy intervals) / window, averaged over chips.
A trace the profiler cut short covers only part of the call, so it
gives no reading."""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or tr["window_s"] <= 0 or tr["n_ops"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
