"""Seconds of set-up spent tracing the cell's fused program from Python
to a jaxpr (``/jax/core/compile/jaxpr_trace_duration``), as the program
recorded it in this process (``repro.core.record``, ``harness.phases``)."""

from harness import phases


def read(ctx):
    return phases.setup_seconds(ctx, "trace_s")
