"""The fused sweep's own instrumentation: scan counters, the step traced
once, name scopes, host spans and the set-up record.

* every result field that existed before the counters is bit for bit
  what it was (digests in ``tests/golden/lane_fields.json``);
* each fused program calls its step function once per policy segment
  while it is traced;
* ``scan_steps`` is the executed chunks times ``chunk`` of the lane's
  segment, ``ceil(max active_steps / chunk) * chunk``, per shard when
  sharded, and ``active_steps`` never exceeds it;
* the compiled fused programs carry the scopes in their op metadata;
* a profiled ``run_sweep`` writes its host spans, nested in
  ``repro.sweep``;
* ``repro.core.record`` holds each fused program's trace, lower and
  load seconds, and a second process sharing a cache directory loads
  the program from it.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import SweepRequest, jax_policies, record, run_sweep  # noqa: E402
from repro.core import jaxplane, tcpjax  # noqa: E402

SRC = Path(__file__).resolve().parents[1] / "src"
GOLDEN_DIR = Path(__file__).resolve().parent / "golden"
sys.path.insert(0, str(GOLDEN_DIR))

import _capture_lane_fields as capture  # noqa: E402

GOLDEN = json.loads(capture.GOLDEN.read_text())
PROGRAM = {"forwarder": "_run_fused_impl", "tcp": "_run_tcp_fused_impl"}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_result_fields_unchanged_bit_for_bit(name):
    got = capture.digests(run_sweep(capture.requests()[name]))
    want = GOLDEN[name]
    assert {k: got[k] for k in want} == want
    # the only new fields are the two counters
    assert {k.split("/")[1] for k in set(got) - set(want)} == {
        "active_steps",
        "scan_steps",
    }


@pytest.mark.parametrize(
    "scenario, module, step",
    [("forwarder", jaxplane, "_claim_step"), ("tcp", tcpjax, "_tcp_step")],
)
def test_fused_program_traces_each_step_once(monkeypatch, scenario, module, step):
    calls = []
    real = getattr(module, step)

    def counted(pol, *args, **kw):
        calls.append(pol.name)
        return real(pol, *args, **kw)

    monkeypatch.setattr(module, step, counted)
    # a shape no other test compiles, so the program is traced here
    kw = dict(n_packets=[19, 23]) if scenario == "tcp" else dict(n_packets=59)
    before = record.program(PROGRAM[scenario])
    run_sweep(SweepRequest(scenario=scenario, seeds=np.arange(3), chunk=8, **kw))
    assert sorted(calls) == sorted(jax_policies())
    after = record.program(PROGRAM[scenario])
    assert after.compiles == (before.compiles if before else 0) + 1


def _check_counters(res, chunk: int, s_pad: int | None = None):
    for p in res.policies:
        active = np.asarray(res[p].active_steps)
        scanned = np.asarray(res[p].scan_steps)
        assert active.dtype == scanned.dtype == np.int32
        assert (active <= scanned).all(), p
        assert (scanned == scanned[0]).all(), p
        want = s_pad if s_pad is not None else -(-active.max() // chunk) * chunk
        assert scanned[0] == want, (p, scanned[0], want)


@pytest.mark.parametrize("chunk", [16, 64])
def test_forwarder_scan_steps_are_the_chunks_run(chunk):
    req = SweepRequest(
        seeds=np.arange(8),
        n_packets=240,
        chunk=chunk,
        lane_params=dict(batch=np.array([1, 2, 4, 8, 8, 16, 32, 64])),
    )
    res = run_sweep(req)
    _check_counters(res, chunk)
    # every claim is one active step here, and the batch-1 lane needs
    # one step per packet
    for p in res.policies:
        np.testing.assert_array_equal(res[p].active_steps, res[p].batches)
    assert np.asarray(res["corec"].active_steps)[0] == 240


def test_wedged_forwarder_lane_counts_the_step_that_found_no_work():
    # a locked lane whose lock holder dies inside the critical section
    # wedges: its last active step claims nothing, so it is no batch
    req = SweepRequest(
        policies=["locked"],
        seeds=np.arange(4),
        n_packets=200,
        fault_params=dict(crash_worker=0, crash_t=5.0),
        lane_params=dict(claim_overhead=2.0),
    )
    res = run_sweep(req)
    _check_counters(res, req.chunk)
    lane = res["locked"]
    wedged = np.asarray(lane.undelivered) > 0
    assert wedged.any()
    active, batches = np.asarray(lane.active_steps), np.asarray(lane.batches)
    assert (active[wedged] == batches[wedged] + 1).all()
    assert (active[~wedged] == batches[~wedged]).all()


def test_tcp_scan_steps_are_the_chunks_run():
    req = SweepRequest(
        scenario="tcp", seeds=np.arange(4), n_packets=[40, 40], t_start=[0.0, 13.0]
    )
    res = run_sweep(req)
    _check_counters(res, req.chunk)
    for p in res.policies:
        assert (np.asarray(res[p].active_steps) > 0).all()


def test_reference_engine_scans_every_step_with_the_same_active_steps():
    kw = dict(seeds=np.arange(3), n_packets=150, lane_params=dict(batch=4))
    ref = run_sweep(SweepRequest(engine="reference", **kw))
    cmp = run_sweep(SweepRequest(**kw))
    _check_counters(ref, 64, s_pad=192)  # 150 claims rounded up to chunks
    for p in ref.policies:
        np.testing.assert_array_equal(ref[p].active_steps, cmp[p].active_steps)


_SHARD_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    import jax
    from repro.core import SweepRequest, run_sweep
    assert jax.local_device_count() == 4
    # lanes 0-3 (shard 0) are batch 64, lanes 4-7 (shard 1) batch 1:
    # the shards run different numbers of chunks
    batch = np.array([64] * 4 + [1] * 4 + [8] * 8)
    req = SweepRequest(policies=["corec"], seeds=np.arange(16), n_packets=200,
                       lane_params=dict(batch=batch), shards=4)
    res = run_sweep(req)["corec"]
    active = np.asarray(res.active_steps).reshape(4, 4)
    scanned = np.asarray(res.scan_steps).reshape(4, 4)
    want = -(-active.max(axis=1) // 64) * 64
    assert (scanned == want[:, None]).all(), (scanned, want)
    assert len(set(want.tolist())) > 1, want
    one = run_sweep(SweepRequest(policies=["corec"], seeds=np.arange(16),
                                 n_packets=200, lane_params=dict(batch=batch)))
    assert (np.asarray(one["corec"].active_steps) == active.reshape(-1)).all()
    print("SHARD-COUNTERS-OK")
    """
)


def _env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_sharded_scan_steps_are_per_shard():
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert "SHARD-COUNTERS-OK" in out.stdout, out.stderr[-3000:]


def _compiled_hlo(monkeypatch, req) -> str:
    """The compiled fused program of ``req``, as HLO text."""
    texts = []
    orig = jaxplane._call_fused

    def grab(fn, args, static, timings):
        texts.append(fn.lower(*args, **static).compile().as_text())
        return orig(fn, args, static, timings)

    monkeypatch.setattr(jaxplane, "_call_fused", grab)
    monkeypatch.setattr(tcpjax, "_call_fused", grab)
    run_sweep(req)
    assert len(texts) == 1
    return texts[0]


@pytest.mark.parametrize("scenario", ["forwarder", "tcp"])
def test_fused_programs_carry_the_scopes(monkeypatch, scenario):
    kw = dict(n_packets=[20, 20]) if scenario == "tcp" else dict(n_packets=64)
    req = SweepRequest(scenario=scenario, seeds=np.arange(2), chunk=32, **kw)
    hlo = _compiled_hlo(monkeypatch, req)
    layers = ("scan", "post_scan") + (("claims",) if scenario == "forwarder" else ())
    for p in jax_policies():
        assert f"/seg.{p}/" in hlo, p
        for layer in layers:
            assert f"/seg.{p}/{layer}/" in hlo, (p, layer)
    assert "/chunk.32/while" in hlo
    assert "/done_prefix/" in hlo
    # the claim records are scattered outside the scan
    assert "/scan/claims/" not in hlo


def test_profiled_sweep_writes_nested_host_spans(tmp_path):
    from jax.profiler import ProfileData

    req = SweepRequest(policies=["corec"], seeds=np.arange(2), n_packets=50)
    run_sweep(req)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run_sweep(req).lanes)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    end = e.start_ns + e.duration_ns
                    spans.setdefault(e.name, []).append((e.start_ns, end))
    assert set(spans) == {"repro.sweep", "repro.prepare", "repro.dispatch"}
    ((lo, hi),) = spans["repro.sweep"]
    for name in ("repro.prepare", "repro.dispatch"):
        assert all(lo <= s and e <= hi for s, e in spans[name]), name
    # the request is prepared before the one dispatch
    ((d_lo, _),) = spans["repro.dispatch"]
    assert max(e for _, e in spans["repro.prepare"]) <= d_lo


def test_record_counts_nested_traces_inside_their_program():
    record.install()

    def _record_probe_inner(x):
        return x * 2

    def _record_probe(x):
        y = jax.eval_shape(_record_probe_inner, x)
        return jax.jit(_record_probe_inner)(x) + jax.numpy.zeros(y.shape)

    jax.jit(_record_probe)(np.ones(3, np.float32))
    got = record.program("_record_probe")
    assert got.compiles == 1
    assert got.trace_s > 0 and got.lower_s > 0 and got.load_s > 0
    assert record.program("_record_probe_inner") is None
    assert "_record_probe" in record.programs()


_CACHE_SCRIPT = textwrap.dedent(
    """
    import dataclasses, json, sys
    import numpy as np
    import jax
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.core import SweepRequest, record, run_sweep
    res = run_sweep(SweepRequest(policies=["corec"], seeds=np.arange(2),
                                 n_packets=40, chunk=8))
    steps = {p: [np.asarray(a).tolist() for a in c]
             for p, c in record.last_sweep().items()}
    print(json.dumps(dict(
        phases=dataclasses.asdict(record.program("_run_fused_impl")),
        last=steps,
        active=np.asarray(res["corec"].active_steps).tolist())))
    """
)


def test_record_reports_set_up_and_a_cache_hit_in_a_second_process(tmp_path):
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, str(tmp_path / "cache")],
            env=_env(),
            capture_output=True,
            text=True,
            timeout=600,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = (r["phases"] for r in runs)
    for phases in (first, second):
        assert phases["compiles"] == 1
        assert phases["trace_s"] > 0 and phases["lower_s"] > 0
        assert phases["load_s"] > 0
    assert first["cache"] == "miss" and first["retrieval_s"] == 0
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert runs[1]["last"]["corec"][0] == runs[1]["active"]
