"""Build a cell's ``SweepRequest`` from its configuration and mix.

The lane grid is the Cartesian product of the mix's axes (knob names in
sorted order) times the mix's seeds per configuration, seeds 0, 1, ...
The run seed (``--seed``) shuffles the order of those lanes: every run
of a cell simulates the same set of lanes, so every run does the same
work (the fused scan runs until its slowest lane is done, and which
lane is slowest depends on its traffic), and the seed decides where
each lane sits in the batch and which lanes the correctness check
samples.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import manifest


def lane_order(seed: int, lanes: int) -> np.ndarray:
    """The permutation of ``lanes`` lanes that run seed ``seed`` gives."""
    return np.random.default_rng(int(seed) % (1 << 63)).permutation(lanes)


def grid(axes: dict, seeds: np.ndarray):
    """Per-lane knob arrays and ``points`` (grid point dict, seed) of the
    Cartesian product ``axes`` x ``seeds``, seed-major per point."""
    names = sorted(axes)
    mesh = np.meshgrid(*[np.asarray(axes[k], dtype=np.float64) for k in names],
                       indexing="ij")
    flat = [m.reshape(-1) for m in mesh]
    n_cfg = flat[0].shape[0] if flat else 1
    lanes = {k: np.repeat(v, seeds.shape[0]) for k, v in zip(names, flat)}
    points = [
        ({k: flat[i][c].item() for i, k in enumerate(names)}, int(s))
        for c in range(n_cfg)
        for s in seeds
    ]
    return lanes, np.tile(seeds, n_cfg), points


@dataclass(frozen=True)
class BuiltSweep:
    """One cell's request and what the harness needs to read its results."""

    request: object  # repro.core.SweepRequest
    points: list  # per lane of one policy segment: (grid point, seed)
    policies: tuple
    lanes_per_policy: int
    packets_per_lane: np.ndarray  # [lanes_per_policy] offered packets
    packets_per_call: int

    @property
    def lanes(self) -> int:
        return self.lanes_per_policy * len(self.policies)


def _knobs(traffic: dict, lanes: dict, groups: tuple) -> dict:
    """Knob dicts per SweepRequest field: the mix's statics, then axes;
    ``groups`` are the knob groups the scenario lets a mix set."""
    out = {}
    for group in groups:
        knobs = dict(traffic.get("static", {}).get(group, {}))
        for k in traffic["axes"].get(group, {}):
            knobs[k] = lanes[k]
        out[group] = knobs
    bad = set(traffic["axes"]) - set(groups)
    bad |= set(traffic.get("static", {})) - set(groups)
    if bad:
        raise ValueError(f"mix sets unknown knob groups {sorted(bad)}")
    return out


def offered_packets(config: dict, knobs: dict, lanes: int) -> np.ndarray:
    """Packets each lane's request offers, by the configuration's
    scenario: the forwarder's packets per lane, or TCP's per-flow
    segments after the lane's ``pkt_budget``."""
    scenario = manifest.scenario(config["scenario"])
    return scenario.offered_packets(config, knobs, lanes)


def build(config: dict, traffic: dict, seed: int) -> BuiltSweep:
    """The cell's one ``SweepRequest`` for run seed ``seed``."""
    from repro.core import SweepRequest

    seeds = np.arange(int(traffic["seeds_per_config"]), dtype=np.uint32)
    axes = {k: v for group in traffic["axes"].values() for k, v in group.items()}
    lanes, lane_seed, points = grid(axes, seeds)
    order = lane_order(seed, lane_seed.shape[0])
    lanes = {k: v[order] for k, v in lanes.items()}
    lane_seed = lane_seed[order]
    points = [points[i] for i in order]
    scenario = manifest.scenario(config["scenario"])
    knobs = _knobs(traffic, lanes, scenario.KNOB_GROUPS)
    policies = tuple(config["policies"])
    common = dict(
        scenario=config["scenario"],
        policies=policies,
        seeds=lane_seed,
        n_workers=int(config["n_workers"]),
        max_batch=int(config["max_batch"]),
        **knobs,
    )
    req = SweepRequest(**common, **scenario.request_fields(config, traffic))
    per_lane = scenario.offered_packets(config, knobs, lane_seed.shape[0])
    return BuiltSweep(
        request=req,
        points=points,
        policies=policies,
        lanes_per_policy=int(lane_seed.shape[0]),
        packets_per_lane=per_lane,
        packets_per_call=int(per_lane.sum()) * len(policies),
    )


def sim_pkts_per_s(packets_per_call: int, calls: int, wall_s: float) -> float:
    """Offered packets of the calls completed in the window over the
    window's wall time."""
    return packets_per_call * calls / wall_s
