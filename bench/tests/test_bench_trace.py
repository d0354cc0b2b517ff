"""The reduction from trace events to per-layer numbers."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import manifest, trace  # noqa: E402

CHIP = "/device:TPU:0"


WHILE = "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %tuple), condition=%c"
BODY = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
SORT = "%sort.2 = f32[8]{0} sort(f32[8]{0} %x), dimensions={0}"
KERNEL = "%done_prefix_packed_pallas.1 = s32[8,1]{1,0} custom-call(u32[8,2] %w)"


def _events():
    # two calls on the host clock (ns); in each, a scan (a while whose
    # body op runs 380 of its 400 ns), a sort after it and the kernel
    spans = [
        ["bench.call", 0.0, 1000.0],
        ["bench.block", 200.0, 1000.0],
        ["bench.call", 1100.0, 2000.0],
        ["bench.block", 1300.0, 2000.0],
    ]
    ops = []
    for t in (0.0, 1100.0):
        ops += [
            [CHIP, WHILE, t + 300.0, 400.0],
            [CHIP, BODY, t + 320.0, 380.0],
            [CHIP, SORT, t + 700.0, 100.0],
            [CHIP, KERNEL, t + 850.0, 50.0],
        ]
    return {"ops": ops, "spans": spans}


def test_window_busy_and_layers():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert r["category_s"]["scan"] == pytest.approx(800e-9)
    assert r["category_s"]["post_scan"] == pytest.approx(200e-9)
    assert r["category_s"]["kernel"] == pytest.approx(100e-9)
    assert r["kernel_events"] == 2


def test_idle_per_call_and_gap_attribution():
    r = trace.reduce(_events())
    # call 1: 1000 ns span, 550 busy; call 2: 900 ns span, 550 busy
    assert [c["idle_s"] for c in r["calls"]] == pytest.approx([450e-9, 350e-9])
    labels = {label for label, _ in r["idle_gaps"]}
    assert labels <= {"bench.call", "bench.block", "between calls"}
    # gaps are cut at span edges: [0, 200) in call 1 before its block
    # and [1100, 1300) in call 2 are the longest
    assert r["idle_gaps"][:2] == [["bench.call", pytest.approx(200e-9)]] * 2
    assert ["between calls", pytest.approx(100e-9)] in r["idle_gaps"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(900e-9)


def test_ops_nested_in_a_while_are_scan_at_any_depth():
    ev = _events()
    cond = "%conditional.3 = (f32[8]) conditional(pred[] %p)"
    add = "%add.4 = f32[8] add()"
    ev["ops"] += [[CHIP, cond, 1420.0, 40.0], [CHIP, add, 1425.0, 10.0]]
    ev["ops"].sort(key=lambda o: (o[2], -o[3]))
    r = trace.reduce(ev)
    # the conditional and its add lie inside fusion.1's interval, itself
    # inside the while: all scan, and busy time does not change
    assert r["category_s"]["scan"] == pytest.approx(800e-9)
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert r["truncated"] is False


def test_truncated_trace_reads_the_recorded_part_only():
    ev = _events()
    ev["ops"] = [o for o in ev["ops"] if o[2] < 1000.0]  # the second call unrecorded
    r = trace.reduce(ev)
    assert r["truncated"] is True
    assert r["window_s"] == pytest.approx(900e-9)
    ctx = dict(trace=r, scenario="forwarder", peak_bytes=None,
               words_shape=(8, 2), peaks=trace.device_peaks("TPU v5 lite"))
    assert r["busy_s"] == pytest.approx(550e-9)
    for m in ("host_gap_ms", "scan_ms.fwd", "post_scan_ms.fwd", "doneprefix_us",
              "doneprefix_roofline", "device_idle_share"):
        assert manifest.metric_reader(m)(ctx) is None


def test_readers_on_the_reduction():
    r = trace.reduce(_events())
    ctx = dict(trace=r, scenario="forwarder", peak_bytes=2_000_000,
               words_shape=(1000, 63), peaks=trace.device_peaks("TPU v5 lite"))
    read = {m: manifest.metric_reader(m)(ctx) for m in (
        "host_gap_ms", "scan_ms.fwd", "post_scan_ms.fwd",
        "doneprefix_us", "doneprefix_roofline", "device_idle_share", "peak_hbm_mb")}
    assert read["host_gap_ms"] == pytest.approx(400e-6)
    assert read["scan_ms.fwd"] == pytest.approx(400e-6)
    assert read["post_scan_ms.fwd"] == pytest.approx(100e-6)
    assert read["doneprefix_us"] == pytest.approx(0.05)
    least = 1000 * 65 * 4 / 819e9
    assert read["doneprefix_roofline"] == pytest.approx(100 * least / 50e-9)
    assert read["device_idle_share"] == pytest.approx(45.0)
    assert read["peak_hbm_mb"] == pytest.approx(2.0)


def test_readers_return_nothing_where_nothing_is_traced():
    r = trace.reduce({"ops": [], "spans": [["bench.call", 0.0, 10.0]]})
    ctx = dict(trace=r, scenario="tcp", peak_bytes=None)
    for m in ("doneprefix_us", "doneprefix_roofline", "device_idle_share",
              "peak_hbm_mb", "scan_ms.fwd"):
        assert manifest.metric_reader(m)(ctx) is None


def test_reduction_of_a_trace_recorded_on_the_chip():
    # one traced call of a small forwarder sweep (two policies, 144
    # lanes of 64 packets) on a TPU v5 lite, as ``trace.load`` read it
    path = BENCH / "tests" / "data" / "trace_tiny.json.gz"
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    r = trace.reduce(events)
    assert r["truncated"] is False
    assert r["kernel_events"] == 1
    assert r["category_s"]["scan"] > 5 * r["category_s"]["post_scan"] > 0
    assert 0 < r["category_s"]["kernel"] < 1e-5
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(r["calls"][0]["span_s"])
    assert sum(r["category_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert {label for label, _ in r["idle_gaps"]} <= {"bench.call", "bench.block"}
