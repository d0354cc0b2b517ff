"""Seconds of set-up spent compiling the cell's fused program or, with
the warm compile cache, computing its cache key, looking it up and
loading the stored executable (``/jax/core/compile/backend_compile_duration``),
as the program recorded it in this process (``repro.core.record``,
``harness.phases``)."""

from harness import phases


def read(ctx):
    return phases.setup_seconds(ctx, "load_s")
