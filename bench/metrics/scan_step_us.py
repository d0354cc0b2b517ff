"""Device microseconds per scan step: the time of the chunk ``while``
ops under the ``scan`` scopes (``chunk.<steps>``) over the steps those
chunks ran, counting only chunks the trace recorded whole, so a trace
the profiler cut short still reads (``harness.scopes``)."""

from harness import scopes


def read(ctx):
    red = scopes.of_run(ctx)
    if red is None or not red["steps"]:
        return None
    return 1e6 * sum(red["chunk_s"].values()) / sum(red["steps"].values())
