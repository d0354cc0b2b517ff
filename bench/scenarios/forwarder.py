"""The open-loop forwarder (``SweepRequest(scenario="forwarder")``).

What the harness needs of a scenario, found by its name: the knob
groups a mix may set, the request fields the configuration fills, the
packets a lane offers, the guarantees every lane is held to, and the
plain reference of a sampled lane with the gaps compared against it.
"""

from __future__ import annotations

import numpy as np

from harness import correct, reference

#: SweepRequest knob groups a mix may set
KNOB_GROUPS = ("lane_params", "traffic_params")

#: statistics compared per sampled lane: the floor under the reference
#: value that a gap is relative to (None: an absolute gap)
STATS = {
    "p50": 1e-12,
    "p99": 1e-12,
    "mean": 1e-12,
    "batches": 1.0,
    "max_distance": 1.0,
    "reorder_pct": None,
}


def request_fields(config: dict, traffic: dict) -> dict:
    return dict(
        arrival=traffic["arrival"],
        n_packets=int(config["packets_per_lane"]),
        n_flows=int(config["n_flows"]),
    )


def offered_packets(config: dict, knobs: dict, lanes: int) -> np.ndarray:
    """Every lane offers the configuration's packets per lane."""
    return np.full(lanes, int(config["packets_per_lane"]), dtype=np.int64)


def guarantee_numbers(built, res, config: dict) -> dict:
    """Exactly-once on every lane: each offered packet claimed once."""
    offered = np.full(built.lanes, int(config["packets_per_lane"]))
    return correct.exact_numbers(res, offered)


def lane_reference(built, config: dict, traffic: dict, lanes: list, dtype):
    """``one(policy, lane, row)``: the plain reference's statistics of
    ``lane`` under ``policy`` in ``dtype``; ``row`` is the lane's place
    in ``lanes``, whose raw variates are drawn here at once."""
    n = int(config["packets_per_lane"])
    chunk = int(built.request.chunk)
    seeds = np.asarray(built.request.seeds)[lanes]
    draws = reference.forwarder_draws(
        seeds, traffic["arrival"], n, int(config["n_flows"]), -(-n // chunk) * chunk
    )

    def one(policy, lane, row):
        return reference.forwarder_lane(
            policy, correct.lane_knobs(traffic, built.points[lane][0]),
            {k: v[row] for k, v in draws.items()}, traffic["arrival"],
            int(config["n_workers"]), int(config["max_batch"]), dtype=dtype,
        )

    return one


def lane_gaps(got: dict, want: dict) -> dict:
    """Each statistic's gap on one sampled lane."""
    return {f: correct.gap(got[f], want[f], floor) for f, floor in STATS.items()}
