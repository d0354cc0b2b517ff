"""Request building and the offered-packet arithmetic of sim_pkts_per_s."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import manifest, sweep  # noqa: E402


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31 + 5, 2**33 + 7])
def test_run_seed_shuffles_one_fixed_set_of_lanes(seed):
    cfg = _load("configs", "tcp-2flow")
    mix = dict(_load("traffic", "tcp-grid"), seeds_per_config=2)
    a = sweep.build(cfg, mix, seed)
    b = sweep.build(cfg, mix, seed + 1)
    again = sweep.build(cfg, mix, seed)
    assert sorted(a.points, key=repr) == sorted(b.points, key=repr)
    assert a.points != b.points
    assert a.points == again.points
    # each lane's knobs and seed travel with it
    for i in (0, 7, len(a.points) - 1):
        point, lane_seed = a.points[i]
        assert a.request.seeds[i] == lane_seed
        assert a.request.tcp_params["link_pps"][i] == point["link_pps"]
        assert a.request.lane_params["batch"][i] == point["batch"]
    assert a.packets_per_call == b.packets_per_call


def test_grid_is_seed_major_per_point():
    seeds = np.array([7, 8], np.uint32)
    lanes, seeds, points = sweep.grid({"b": [1, 2], "a": [3.0]}, seeds)
    assert seeds.tolist() == [7, 8, 7, 8]
    assert lanes["b"].tolist() == [1, 1, 2, 2]
    assert points[1] == ({"a": 3.0, "b": 1.0}, 8)


def test_forwarder_packets_per_call():
    cfg = _load("configs", "l3fwd-4w")
    mix = dict(_load("traffic", "udp-grid"), seeds_per_config=2)
    built = sweep.build(cfg, mix, 12345)
    assert built.lanes_per_policy == 6 * 4 * 3 * 2
    assert built.lanes == built.lanes_per_policy * 5
    assert built.packets_per_call == built.lanes * 2000
    assert built.request.n_packets == 2000 and built.request.arrival == "poisson"


def test_tcp_packets_per_call_after_pkt_budget():
    cfg = _load("configs", "tcp-2flow")
    mix = dict(_load("traffic", "tcp-grid"), seeds_per_config=1)
    built = sweep.build(cfg, mix, 2**31 + 3)
    budget = np.asarray(built.request.tcp_params["pkt_budget"])
    want = np.where(budget >= 128, 256, 2 * 48)
    assert sorted(budget.tolist()) == [48] * 72 + [1 << 30] * 72
    assert built.packets_per_lane.tolist() == want.tolist()
    assert built.packets_per_call == int(want.sum()) * 5
    assert built.request.tcp_params["sack"] is False


def test_offered_packets_clamps_budget_per_flow():
    cfg = {"scenario": "tcp", "flow_packets": [128, 20]}
    knobs = {"tcp_params": {"pkt_budget": np.array([1 << 30, 48, 0])}}
    assert sweep.offered_packets(cfg, knobs, 3).tolist() == [148, 68, 0]


def test_unknown_scenario_refused():
    cfg = dict(_load("configs", "tcp-2flow"), scenario="serving-x")
    mix = dict(_load("traffic", "tcp-grid"), scenario="serving-x")
    with pytest.raises(manifest.ManifestError):
        sweep.build(cfg, mix, 1)


@pytest.mark.parametrize(
    "packets,calls,wall,want", [(1000, 3, 2.0, 1500.0), (2_580_480, 4, 40.0, 258048.0)]
)
def test_sim_pkts_per_s(packets, calls, wall, want):
    assert sweep.sim_pkts_per_s(packets, calls, wall) == pytest.approx(want)
