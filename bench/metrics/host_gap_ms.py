"""Device-idle milliseconds per call inside the benchmark's host span
around the call (``bench.call``): host work of ``run_sweep`` that the
device waits for."""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or not tr["calls"]:
        return None
    return 1e3 * sum(c["idle_s"] for c in tr["calls"]) / len(tr["calls"])
