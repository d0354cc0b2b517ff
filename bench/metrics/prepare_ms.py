"""Host milliseconds per call in the program's ``repro.prepare`` spans:
request dicts, knob broadcast and lane padding before the jitted call
(``harness.scopes``)."""

from harness import scopes


def read(ctx):
    red = scopes.of_run(ctx)
    if red is None or "repro.prepare" not in red["span_s"]:
        return None
    return 1e3 * red["span_s"]["repro.prepare"] / red["n_calls"]
