"""The program's marks in a trace: scopes, scan chunks and host spans
(``harness.scopes``), its own record (``harness.phases``), and the
readers built on them."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import manifest, phases, scopes, trace  # noqa: E402

CHIP = "/device:TPU:0"
DATA = BENCH / "tests" / "data"
TRACE_READERS = ("scan_step_us", "prepare_ms", "claims_ms.fwd")
RECORD_READERS = ("setup_trace_s", "setup_lower_s", "setup_load_s", "lane_step_use")
NEW_READERS = TRACE_READERS + RECORD_READERS
OLD_READERS = ("host_gap_ms", "scan_ms.fwd", "post_scan_ms.fwd", "doneprefix_us",
               "doneprefix_roofline", "device_idle_share", "peak_hbm_mb")

WHILE = "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %tuple), condition=%c"
CHUNK = "%while.2 = (s32[], f32[8]) while((s32[], f32[8]) %tuple.2), condition=%d"
BODY = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
COPY = "%copy.3 = f32[8]{0} copy(f32[8]{0} %q)"
SCATTER = "%scatter.4 = f32[8]{0} scatter(f32[8]{0} %r), update_window_dims={}"
SORT = "%sort.2 = f32[8]{0} sort(f32[8]{0} %x), dimensions={0}"
KERNEL = "%done_prefix_packed_pallas.1 = s32[8,1]{1,0} custom-call(u32[8,2] %w)"
SETUP = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %s), kind=kLoop"


def _events():
    """One call: set-up of two segments, a scan of two 4-step chunks in
    segment a, an unscoped copy inside the first chunk, an op merged
    from both segments' scans, the scatter of claims, the post-scan
    sort and the kernel; host spans around it."""
    spans = [
        ["bench.call", 0.0, 2000.0],
        ["repro.sweep", 10.0, 1200.0],
        ["repro.prepare", 20.0, 220.0],
        ["repro.dispatch", 230.0, 300.0],
        ["bench.block", 1200.0, 2000.0],
    ]
    ops = [
        [CHIP, SETUP, 300.0, 20.0, "seg.a"],
        [CHIP, SETUP, 320.0, 20.0, "seg.b"],
        [CHIP, WHILE, 400.0, 500.0, "seg.a/scan"],
        [CHIP, CHUNK, 400.0, 200.0, "seg.a/scan/chunk.4"],
        [CHIP, BODY, 410.0, 100.0, "seg.a/scan"],
        [CHIP, COPY, 520.0, 40.0, ""],
        [CHIP, CHUNK, 650.0, 200.0, "seg.a/scan/chunk.4"],
        [CHIP, BODY, 950.0, 100.0, "seg.a/scan;seg.b/scan"],
        [CHIP, SCATTER, 1300.0, 300.0, "seg.a/claims"],
        [CHIP, SORT, 1750.0, 100.0, "seg.a/post_scan"],
        [CHIP, KERNEL, 1900.0, 50.0, "done_prefix"],
    ]
    return {"ops": ops, "spans": spans}


def test_scope_of_reads_segment_layer_and_chunk():
    root = "jit(_run_fused_impl)"
    assert scopes.scope_of(f"{root}/seg.corec/scan/while/body/add") == "seg.corec/scan"
    assert scopes.scope_of(f"{root}/seg.corec/claims/scatter") == "seg.corec/claims"
    assert scopes.scope_of(f"{root}/seg.adaptive-batch/jit(sort)/sort") == (
        "seg.adaptive-batch")
    assert scopes.scope_of(f"{root}/done_prefix/pallas_call") == "done_prefix"
    assert scopes.scope_of("copy") == "" and scopes.scope_of("") == ""
    chunk = f"{root}/seg.locked/scan/while/body/cond/branch_0_fun/chunk.64/while"
    assert scopes.scope_of(chunk) == "seg.locked/scan/chunk.64"
    # an op inside the chunk is not the chunk
    assert scopes.scope_of(chunk + "/body/mul") == "seg.locked/scan"
    merged = (f"{root}/seg.b/scan/while/body/{root}/seg.a/scan/while/body/"
              f"{root}/seg.b/scan/x")
    assert scopes.scope_of(merged) == "seg.b/scan;seg.a/scan"
    # a chunk's while that XLA folded into its cond carries both names
    cond = f"{root}/seg.locked/scan/while/body/cond"
    assert scopes.scope_of(f"{cond}/{chunk}") == "seg.locked/scan/chunk.64"
    two = f"{chunk}/{root}/seg.corec/scan/cond/branch_0_fun/chunk.64/while"
    assert scopes.scope_of(two) == "seg.locked/scan/chunk.64;seg.corec/scan/chunk.64"


def test_reduce_splits_time_by_layer():
    r = scopes.reduce(_events())
    assert r["truncated"] is False and r["n_calls"] == 1
    # own time: the while's 500 ns hold the two chunks (400) and 100 of
    # its own; the first chunk's 200 hold the body (100) and the copy
    # (40), which takes the chunk's scope; the merged body is scan twice
    assert r["layer_s"]["scan"] == pytest.approx(600e-9)
    assert r["layer_s"]["claims"] == pytest.approx(300e-9)
    assert r["layer_s"]["post_scan"] == pytest.approx(100e-9)
    assert r["layer_s"]["done_prefix"] == pytest.approx(50e-9)
    # the segments' set-up is under no layer
    assert r["layer_s"]["unscoped"] == pytest.approx(40e-9)
    assert r["total_s"] == pytest.approx(1090e-9)
    assert sum(r["layer_s"].values()) == pytest.approx(r["total_s"])
    assert r["chunks"] == {"seg.a": 2} and r["steps"] == {"seg.a": 8}
    assert r["chunk_s"]["seg.a"] == pytest.approx(400e-9)


def test_op_merged_from_two_layers_splits_its_time():
    ev = _events()
    ev["ops"].append([CHIP, SORT, 1950.0, 40.0, "seg.a/claims;seg.b/post_scan"])
    r = scopes.reduce(ev)
    assert r["layer_s"]["claims"] == pytest.approx(320e-9)
    assert r["layer_s"]["post_scan"] == pytest.approx(120e-9)


def test_one_while_running_two_segments_chunks_counts_for_both():
    ev = _events()
    both = "seg.a/scan/chunk.4;seg.b/scan/chunk.4"
    ev["ops"].append([CHIP, CHUNK, 1060.0, 80.0, both])
    r = scopes.reduce(ev)
    assert r["chunks"] == {"seg.a": 3, "seg.b": 1}
    assert r["steps"] == {"seg.a": 12, "seg.b": 4}
    assert r["chunk_s"]["seg.a"] == pytest.approx(440e-9)
    assert r["chunk_s"]["seg.b"] == pytest.approx(40e-9)


def test_reduce_matches_the_nesting_reduction():
    ev = _events()
    nest = trace.reduce({"ops": [o[:4] for o in ev["ops"]], "spans": ev["spans"]})
    r = scopes.reduce(ev)
    assert r["total_s"] == pytest.approx(sum(nest["category_s"].values()))
    assert r["layer_s"]["done_prefix"] == pytest.approx(nest["category_s"]["kernel"])
    # every op nested in a while is under scan; the scan scope adds
    # the merged body op, which runs outside any while here
    assert r["layer_s"]["scan"] == pytest.approx(nest["category_s"]["scan"] + 100e-9)
    assert r["window_s"] == pytest.approx(nest["window_s"])


def test_spans_and_idle_by_innermost_span():
    r = scopes.reduce(_events())
    assert r["span_s"] == pytest.approx({
        "repro.sweep": 1190e-9, "repro.prepare": 200e-9, "repro.dispatch": 70e-9})
    # idle before the first op: 10 ns in bench.call alone, 200 in
    # prepare, 70 in dispatch, 20 in repro.sweep outside both; then the
    # sweep's gaps between ops (260) and the block's (350)
    assert r["idle_s"] == pytest.approx({
        "bench.call": 10e-9, "repro.sweep": 280e-9, "repro.prepare": 200e-9,
        "repro.dispatch": 70e-9, "bench.block": 350e-9})
    nest = trace.reduce({"ops": [o[:4] for o in _events()["ops"]],
                         "spans": _events()["spans"]})
    assert sum(r["idle_s"].values()) == pytest.approx(nest["calls"][0]["idle_s"])


def test_old_four_field_records_are_unscoped():
    ev = _events()
    ev["ops"] = [o[:4] for o in ev["ops"]]
    r = scopes.reduce(ev)
    assert r["chunks"] == {}
    assert r["layer_s"]["unscoped"] == pytest.approx(r["total_s"])


def _cut(events, t):
    """``events`` as a profiler that stopped recording at ``t`` leaves
    them: no op that ended later; the host spans run on."""
    return dict(events, ops=[o for o in events["ops"] if o[2] + o[3] <= t])


def test_truncated_trace_counts_only_whole_chunks():
    r = scopes.reduce(_cut(_events(), 700.0))
    assert r["truncated"] is True
    assert r["chunks"] == {"seg.a": 1} and r["steps"] == {"seg.a": 4}


def _ctx_for(monkeypatch, events, scenario="forwarder"):
    """A reader's ctx over ``events``, with ``scopes`` reading them in
    place of a trace file."""
    monkeypatch.setattr(scopes, "latest_trace", lambda *a: "trace.xplane.pb")
    monkeypatch.setattr(scopes, "load", lambda p: events)
    old = {"ops": [o[:4] for o in events["ops"]], "spans": events["spans"]}
    return dict(trace=trace.reduce(old), scenario=scenario, peak_bytes=None,
                words_shape=(8, 2), peaks=trace.device_peaks("TPU v5 lite"))


def test_trace_readers(monkeypatch):
    ctx = _ctx_for(monkeypatch, _events())
    read = {m: manifest.metric_reader(m)(ctx) for m in TRACE_READERS}
    # the two chunk whiles, 400 ns, over their 8 steps
    assert read["scan_step_us"] == pytest.approx(1e6 * 400e-9 / 8)
    assert read["prepare_ms"] == pytest.approx(200e-6)
    assert read["claims_ms.fwd"] == pytest.approx(300e-6)
    tcp = _ctx_for(monkeypatch, _events(), scenario="tcp")
    assert manifest.metric_reader("claims_ms.fwd")(tcp) is None
    assert manifest.metric_reader("scan_step_us")(tcp) == read["scan_step_us"]


def test_trace_readers_on_a_truncated_trace(monkeypatch):
    ctx = _ctx_for(monkeypatch, _cut(_events(), 700.0))
    assert ctx["trace"]["truncated"] is True
    # one whole chunk of 4 steps, 200 ns
    assert manifest.metric_reader("scan_step_us")(ctx) == pytest.approx(0.05)
    assert manifest.metric_reader("prepare_ms")(ctx) == pytest.approx(200e-6)
    assert manifest.metric_reader("claims_ms.fwd")(ctx) is None


def test_trace_readers_give_nothing_for_another_runs_trace(monkeypatch):
    ctx = _ctx_for(monkeypatch, _events())
    ctx["trace"] = dict(ctx["trace"], window_s=ctx["trace"]["window_s"] * 2)
    for m in TRACE_READERS:
        assert manifest.metric_reader(m)(ctx) is None


def test_trace_readers_give_nothing_without_a_trace(monkeypatch):
    monkeypatch.setattr(scopes, "latest_trace", lambda *a: None)
    ctx = dict(trace={"window_s": 1.0}, scenario="forwarder")
    for m in TRACE_READERS:
        assert manifest.metric_reader(m)(ctx) is None


class _Record:
    """The program's record as ``repro.core.record`` gives it."""

    def __init__(self, programs, last):
        self._programs, self._last = programs, last

    def program(self, name):
        return self._programs.get(name)

    def last_sweep(self):
        return dict(self._last)


class _Phases:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_record_readers(monkeypatch):
    import numpy as np

    fwd = _Phases(compiles=1, trace_s=3.5, lower_s=1.25, load_s=2.0)
    last = {"corec": (np.array([30, 64]), np.array([64, 64])),
            "locked": (np.array([10, 22]), np.array([32, 32]))}
    monkeypatch.setattr(phases, "_record",
                        lambda: _Record({"_run_fused_impl": fwd}, last))
    ctx = dict(scenario="forwarder")
    read = {m: manifest.metric_reader(m)(ctx) for m in RECORD_READERS}
    assert read == {"setup_trace_s": 3.5, "setup_lower_s": 1.25, "setup_load_s": 2.0,
                    "lane_step_use": pytest.approx(100.0 * 126 / 192)}
    # no record of the TCP program in this process
    for m in ("setup_trace_s", "setup_lower_s", "setup_load_s"):
        assert manifest.metric_reader(m)(dict(scenario="tcp")) is None


def test_record_readers_give_nothing_without_the_record(monkeypatch):
    monkeypatch.setattr(phases, "_record", lambda: None)
    for m in RECORD_READERS:
        assert manifest.metric_reader(m)(dict(scenario="forwarder")) is None


def _tiny():
    with gzip.open(DATA / "trace_tiny.json.gz", "rt") as f:
        return json.load(f)


def test_existing_readers_unchanged_on_the_old_chip_trace(monkeypatch):
    # the values every existing reader gave on this trace before the
    # program had scopes; a trace of a program without them gives none
    # of the new trace metrics
    ev = _tiny()
    ctx = _ctx_for(monkeypatch, ev)
    ctx.update(peak_bytes=275203584, words_shape=(144, 2))
    want = {
        "host_gap_ms": 41.976437000000004,
        "scan_ms.fwd": 7.2192230000001025,
        "post_scan_ms.fwd": 0.9064910000000009,
        "doneprefix_us": 0.20600000000000002,
        "doneprefix_roofline": 1.3656246665955403,
        "device_idle_share": 83.78136182295775,
        "peak_hbm_mb": 275.203584,
    }
    assert {m: manifest.metric_reader(m)(ctx) for m in OLD_READERS} == want
    for m in TRACE_READERS:
        assert manifest.metric_reader(m)(ctx) is None


def test_hlo_op_names_from_a_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("seg.p"):
            with jax.named_scope("scan"):
                y = jax.lax.fori_loop(0, 3, lambda i, c: c * 2.0 + 1.0, x)
            with jax.named_scope("claims"):
                y = y.at[0].set(1.0)
            with jax.named_scope("post_scan"):
                return jnp.sort(y)

    x = jnp.arange(8.0)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    path = scopes.latest_trace(tmp_path)
    names = scopes.hlo_op_names(Path(path).read_bytes())
    module = next(m for m in names if m.startswith("jit_f"))
    found = {scopes.scope_of(n) for n in names[module].values()}
    assert {"seg.p/scan", "seg.p/claims", "seg.p/post_scan"} <= found


def test_every_chunk_while_of_a_fused_program_keeps_its_mark(tmp_path):
    jax = pytest.importorskip("jax")
    import numpy as np

    from repro.core import SweepRequest, run_sweep

    req = SweepRequest(policies=["corec", "scaleout"], seeds=np.arange(2),
                       n_packets=48, chunk=16)
    run_sweep(req)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run_sweep(req).lanes)
    names = scopes.hlo_op_names(Path(scopes.latest_trace(tmp_path)).read_bytes())
    module = next(m for m in names if "_run_fused_impl" in m)
    chunk_whiles = [o for o in names[module].values() if scopes.CHUNK_RE.search(o)]
    assert chunk_whiles
    marked = {s for o in chunk_whiles for s in scopes.scope_of(o).split(";")}
    assert marked == {"seg.corec/scan/chunk.16", "seg.scaleout/scan/chunk.16"}


# ----------------------------------------------------------------------
# a trace recorded on the chip with the program's scopes and spans
# ----------------------------------------------------------------------
def _scoped():
    """One traced call of a small forwarder sweep (five policies, 40
    lanes of 128 packets, claim caps 1-64, chunks of 32 steps) on a
    TPU v5 lite, as ``scopes.load`` read it."""
    with gzip.open(DATA / "trace_scoped.json.gz", "rt") as f:
        return json.load(f)


def _nesting(ev):
    return trace.reduce({"ops": [o[:4] for o in ev["ops"]], "spans": ev["spans"]})


def test_scoped_chip_trace_scan_scope_is_the_nesting_scan():
    ev = _scoped()
    nest, r = _nesting(ev), scopes.reduce(ev)
    assert r["truncated"] is False and nest["truncated"] is False
    cat = nest["category_s"]
    assert r["layer_s"]["scan"] == pytest.approx(cat["scan"], rel=0.01)
    # the scatter of claims runs after the scan's while, so the nesting
    # split counts it as post-scan
    assert r["layer_s"]["claims"] > 0
    assert r["layer_s"]["claims"] + r["layer_s"]["post_scan"] <= cat["post_scan"]


def test_scoped_chip_trace_layers_sum_to_op_time():
    ev = _scoped()
    nest, r = _nesting(ev), scopes.reduce(ev)
    total = sum(nest["category_s"].values())
    assert r["total_s"] == pytest.approx(total, rel=1e-9)
    assert sum(r["layer_s"].values()) == pytest.approx(total, rel=1e-9)
    # the scope holds the kernel and the reshapes around its launch
    kernel = nest["category_s"]["kernel"]
    assert kernel <= r["layer_s"]["done_prefix"] < 1e-5


def test_scoped_chip_trace_counts_the_chunks_that_ran():
    r = scopes.reduce(_scoped())
    # the counters of the same call read scan_steps 128 in every
    # segment: four chunks of 32 steps each
    segs = ("corec", "scaleout", "locked", "hybrid", "adaptive-batch")
    assert r["chunks"] == {f"seg.{p}": 4 for p in segs}
    assert r["steps"] == {f"seg.{p}": 128 for p in segs}
    assert sum(r["chunk_s"].values()) <= r["layer_s"]["scan"]


def test_scoped_chip_trace_idle_gaps_carry_program_spans():
    ev = _scoped()
    r, nest = scopes.reduce(ev), _nesting(ev)
    host_gap = sum(c["idle_s"] for c in nest["calls"])
    assert sum(r["idle_s"].values()) == pytest.approx(host_gap, rel=1e-9)
    assert r["idle_s"]["repro.prepare"] >= 0.9 * (host_gap - r["idle_s"]["bench.block"])
    assert max(r["idle_s"], key=r["idle_s"].get) == "repro.prepare"


def test_truncated_copy_of_the_scoped_chip_trace_gives_scan_step_us(monkeypatch):
    ev = _scoped()
    full = manifest.metric_reader("scan_step_us")(_ctx_for(monkeypatch, ev))
    # the profiler stopped as the second chunk began
    starts = sorted(o[2] for o in ev["ops"] if "/chunk." in o[4])
    ctx = _ctx_for(monkeypatch, _cut(ev, starts[1]))
    assert ctx["trace"]["truncated"] is True
    red = scopes.of_run(ctx)
    assert 0 < sum(red["chunks"].values()) < sum(scopes.reduce(ev)["chunks"].values())
    got = manifest.metric_reader("scan_step_us")(ctx)
    assert got == pytest.approx(full, rel=0.5)
    assert manifest.metric_reader("prepare_ms")(ctx) is not None
    assert manifest.metric_reader("claims_ms.fwd")(ctx) is None
