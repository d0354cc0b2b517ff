"""Device milliseconds per call of the serving branches of the claim
step, the autoscale wake gate and shed-at-claim admission: the ops
under the ``serve`` scope (``harness.opscopes``)."""

from harness import opscopes


def read(ctx):
    return opscopes.scope_ms(ctx, "serve")
