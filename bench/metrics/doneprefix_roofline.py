"""The done-prefix kernel's share of its roofline, in percent.

The kernel reads each lane's packed claim words and limit and writes
one prefix per lane: ``rows * (words + 2) * 4`` bytes per call, from the
result shapes, and no arithmetic worth counting, so it is bound by
memory: the least time is those bytes over the chip's HBM bandwidth.
"""


def read(ctx):
    tr = ctx["trace"]
    if tr["truncated"] or tr["kernel_events"] == 0:
        return None
    rows, words = ctx["words_shape"]
    least_s = rows * (words + 2) * 4 / ctx["peaks"]["hbm_bytes_per_s"]
    per_call_s = tr["category_s"]["kernel"] / len(tr["calls"])
    return 100.0 * least_s / per_call_s
