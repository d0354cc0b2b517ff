"""Closed-loop TCP through the forwarder (``SweepRequest(scenario="tcp")``).

What the harness needs of a scenario, found by its name: the knob
groups a mix may set, the request fields the configuration fills, the
packets a lane offers, the guarantees every lane is held to, and the
plain reference of a sampled lane with the gaps compared against it.
"""

from __future__ import annotations

import numpy as np

from harness import correct, reference

#: SweepRequest knob groups a mix may set
KNOB_GROUPS = ("lane_params", "tcp_params")

#: per-lane counts compared as one relative gap
COUNTS = ("sends", "batches", "items", "retransmissions", "spurious",
          "delivered", "deschedules")


def request_fields(config: dict, traffic: dict) -> dict:
    return dict(
        n_packets=np.asarray(config["flow_packets"], dtype=np.int32),
        t_start=np.asarray(config["flow_start"], dtype=np.float32),
    )


def _budgeted(config: dict, budget, lanes: int) -> np.ndarray:
    """``[lanes, flows]`` segments each flow offers after the lane's
    ``pkt_budget``."""
    flow = np.asarray(config["flow_packets"], dtype=np.int64)
    budget = np.broadcast_to(np.asarray(budget, dtype=np.float64), (lanes,))
    budget = np.maximum(budget.astype(np.int64), 0)
    return np.minimum(flow[None, :], budget[:, None])


def offered_packets(config: dict, knobs: dict, lanes: int) -> np.ndarray:
    """Each lane's per-flow segments after its ``pkt_budget``, summed."""
    budget = knobs["tcp_params"].get("pkt_budget", 1 << 30)
    return _budgeted(config, budget, lanes).sum(axis=1)


def guarantee_numbers(built, res, config: dict) -> dict:
    """Exactly-once on every lane (each transmission claimed once), and
    delivery: each flow done, with every segment the lane's
    ``pkt_budget`` leaves it delivered."""
    vals = correct.exact_numbers(res, correct.cat(res, "sends").astype(np.int64))
    budget = built.request.tcp_params.get("pkt_budget", 1 << 30)
    neff = np.tile(_budgeted(config, budget, built.lanes_per_policy),
                   (len(res.policies), 1))
    vals["undone_flows"] = int((~correct.cat(res, "done")).sum())
    vals["delivery_bad_flows"] = int((correct.cat(res, "delivered") != neff).sum())
    return vals


def budgets(request) -> tuple:
    """(tx_budget, scan steps) of a TCP request: the sweep's documented
    defaults, 9/8 of the packets + 32 and 3 per transmission + flows +
    64, the steps rounded up to whole chunks."""
    flows = np.asarray(request.n_packets)
    total = int(flows.sum())
    tx = request.tx_budget or total + total // 8 + 32
    steps = request.n_steps or 3 * tx + flows.shape[0] + 64
    chunk = int(request.chunk)
    return int(tx), -(-int(steps) // chunk) * chunk


def lane_reference(built, config: dict, traffic: dict, lanes: list, dtype):
    """``one(policy, lane, row)``: the plain reference's statistics of
    ``lane`` under ``policy`` in ``dtype``; ``row`` is the lane's place
    in ``lanes``, whose raw variates are drawn here at once."""
    tx, steps = budgets(built.request)
    draws = reference.tcp_draws(np.asarray(built.request.seeds)[lanes], tx, steps)

    def one(policy, lane, row):
        return reference.tcp_lane(
            policy, correct.lane_knobs(traffic, built.points[lane][0]),
            {k: v[row] for k, v in draws.items()},
            config["flow_packets"], config["flow_start"],
            int(config["n_workers"]), int(config["max_batch"]), tx, steps,
            dtype=dtype,
        )

    return one


def lane_gaps(got: dict, want: dict) -> dict:
    """Flow completion times, and the counts as one relative gap, on
    one sampled lane."""
    return {
        "fct": correct.gap(got["fct"], want["fct"], 1e-12),
        "counts": max(correct.gap(got[f], want[f], 1.0) for f in COUNTS),
    }
