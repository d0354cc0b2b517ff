"""Seconds of set-up spent lowering the cell's fused program from its
jaxpr to the MLIR module
(``/jax/core/compile/jaxpr_to_mlir_module_duration``), as the program
recorded it in this process (``repro.core.record``, ``harness.phases``)."""

from harness import phases


def read(ctx):
    return phases.setup_seconds(ctx, "lower_s")
