"""Device microseconds per call of the done-prefix kernel: the summed
durations of its events in the trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or tr["kernel_events"] == 0:
        return None
    return 1e6 * tr["category_s"]["kernel"] / len(tr["calls"])
