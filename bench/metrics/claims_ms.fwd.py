"""Device milliseconds per call of the forwarder's scatter of claim
records into per-packet completions: the ops under the ``claims``
scopes (``harness.scopes``)."""

from harness import scopes


def read(ctx):
    if ctx["scenario"] != "forwarder":
        return None
    return scopes.layer_ms(ctx, "claims")
