"""Open-loop serving (``SweepRequest(scenario="serving")``).

What the harness needs of a scenario, found by its name: the knob
groups a mix may set, the request fields the configuration fills, the
users a lane offers, the guarantees every lane is held to, and the
plain reference of a sampled lane with the gaps compared against it.

Each packet slot is one user.  ``capacity`` users are drawn per lane
and, with the generation horizon left at +inf and no retry or hedge
copies, every one of them is offered, so a lane offers ``capacity``
users before the first call.  Under admission a shed user burns its
claim bit but is never served, so exactly-once reads
``claimed_popcount == items + shed`` rather than ``== offered``.
"""

from __future__ import annotations

import numpy as np

from harness import correct, reference, serving_reference

#: SweepRequest knob groups a mix may set
KNOB_GROUPS = ("lane_params", "traffic_params", "serving_params")

#: statistics compared per sampled lane: the floor under the reference
#: value that a gap is relative to (None: an absolute gap)
STATS = {
    "p50": 1e-12,
    "p99": 1e-12,
    "mean": 1e-12,
    "slo_attained": None,
    "batches": 1.0,
}
#: per-lane counts compared as one relative gap
COUNTS = ("shed", "undelivered")


def request_fields(config: dict, traffic: dict) -> dict:
    base = traffic.get("static", {}).get("serving_params", {}).get("base_workers")
    want = config["base_workers"]
    if base != want:
        raise ValueError(f"mix base_workers {base!r}, configuration {want!r}")
    return dict(
        arrival=traffic["arrival"],
        service=config["service"],
        n_packets=int(config["capacity"]),
        n_flows=int(config["n_flows"]),
        use_policy_serving_defaults=bool(config["use_policy_serving_defaults"]),
    )


def offered_packets(config: dict, knobs: dict, lanes: int) -> np.ndarray:
    """Every lane offers its whole capacity: users it draws."""
    return np.full(lanes, int(config["capacity"]), dtype=np.int64)


def _stranding_bound(built, config: dict) -> np.ndarray:
    """Per lane of one segment, the most users per-core queues may
    strand: below each autoscaled worker's gate, ``threshold - 1``."""
    sp = built.request.serving_params
    base = float(config["base_workers"])
    per = np.asarray(sp.get("scale_backlog", np.inf), dtype=np.float64)
    per = np.broadcast_to(np.maximum(per, 1.0), (built.lanes_per_policy,))
    out = np.zeros(built.lanes_per_policy, np.int64)
    for w in range(int(config["n_workers"])):
        if w >= base:
            out += np.clip((w - base + 1.0) * per, 1.0, 2.0**30).astype(np.int64) - 1
    return out


def guarantee_numbers(built, res, config: dict) -> dict:
    """On every lane: each claimed user claimed once (popcount equals
    served plus shed), the kernel's prefix equal to the plain prefix of
    the same words capped at served plus shed, the whole capacity
    offered, and every offered user served, shed or stranded.  Only
    per-core queues without stealing may strand users, and only below an
    autoscaled worker's gate."""
    pop, pre, items, shed, offered, undelivered = (
        correct.cat(res, f).astype(np.int64)
        for f in ("claimed_popcount", "claimed_prefix", "items", "shed",
                  "offered", "undelivered")
    )
    claimed = items + shed
    plain = reference.done_prefix(correct.cat(res, "claimed_words"), claimed)
    may_strand = _stranding_bound(built, config)
    none = np.zeros_like(may_strand)
    bound = np.concatenate([
        none if reference.POLICIES[p][0] or reference.POLICIES[p][2] else may_strand
        for p in res.policies
    ])
    return {
        "exactly_once_bad_lanes": int(
            ((pop != claimed) | (offered != claimed + undelivered)).sum()
        ),
        "prefix_mismatch_lanes": int((plain != pre).sum()),
        "offered_bad_lanes": int((offered != int(config["capacity"])).sum()),
        "stranded_bad_lanes": int(((undelivered < 0) | (undelivered > bound)).sum()),
    }


def lane_reference(built, config: dict, traffic: dict, lanes: list, dtype):
    """``one(policy, lane, row)``: the plain reference's statistics of
    ``lane`` under ``policy`` in ``dtype``; ``row`` is the lane's place
    in ``lanes``, whose raw variates are drawn here at once."""
    req = built.request
    n = int(config["capacity"])
    chunk = int(req.chunk)
    budget = n if req.claim_budget is None else min(int(req.claim_budget), n)
    draws = serving_reference.serving_draws(
        np.asarray(req.seeds)[lanes], n, int(config["n_flows"]),
        -(-budget // chunk) * chunk,
    )

    def one(policy, lane, row):
        return serving_reference.serving_lane(
            policy, correct.lane_knobs(traffic, built.points[lane][0]),
            {k: v[row] for k, v in draws.items()},
            int(config["n_workers"]), int(config["max_batch"]), dtype=dtype,
        )

    return one


def lane_gaps(got: dict, want: dict) -> dict:
    """Each statistic's gap, and the counts as one relative gap, on one
    sampled lane."""
    gaps = {f: correct.gap(got[f], want[f], floor) for f, floor in STATS.items()}
    gaps["counts"] = max(correct.gap(got[f], want[f], 1.0) for f in COUNTS)
    return gaps
