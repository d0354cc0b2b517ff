"""Device milliseconds per call of the forwarder claim scan
(``jaxplane._sweep_core``): ops nested in the scan's ``while`` op, and
the loop's own control where no body op runs."""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or ctx["scenario"] != "forwarder" or not tr["calls"]:
        return None
    return 1e3 * tr["category_s"]["scan"] / len(tr["calls"])
