"""Device milliseconds per call of the lane set-up, traffic generation
and per-queue views, of every policy segment: the ops under the
``lane_setup`` scope (``harness.opscopes``)."""

from harness import opscopes


def read(ctx):
    return opscopes.scope_ms(ctx, "lane_setup")
