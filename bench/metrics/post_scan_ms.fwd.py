"""Device milliseconds per call of the forwarder's post-scan work
(completion reconstruction, reorder metrics, percentiles): ops outside
the scan's ``while`` op and outside the done-prefix kernel."""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or ctx["scenario"] != "forwarder" or not tr["calls"]:
        return None
    return 1e3 * tr["category_s"]["post_scan"] / len(tr["calls"])
