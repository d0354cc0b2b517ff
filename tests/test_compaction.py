"""Claim-compacted engines vs their per-claim reference formulations.

The compacted engines (:mod:`repro.core.jaxplane` /
:mod:`repro.core.tcpjax`, ``engine="compacted"``) restructure the hot
loop — claim records + one post-scan scatter instead of in-step
completion writes, chunked scans with a ``done``/quiesce
short-circuit, per-policy segments fused into one jitted call — while
``engine="reference"`` keeps the pre-compaction per-claim scan.  These
tests pin the two BIT-IDENTICAL for every registry policy on both the
forwarder and the TCP plane (completions, reorder metrics, FCT, retx,
counters and the packed-bitmap invariants all included), plus:

* a fused multi-policy call equals the same policies run one at a
  time,
* a tight ``claim_budget`` fails loudly (exactly-once counters short)
  instead of silently truncating,
* the sharded lane axis (``shard_map`` over forced host devices)
  equals the unsharded run bit for bit — exercised in a subprocess so
  ``XLA_FLAGS=--xla_force_host_platform_device_count`` is set before
  jax initializes, the same way CI forces multi-device CPU.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import jax_policies  # noqa: E402
from repro.core.jaxplane import LaneResult, _fused_lanes, run_lanes  # noqa: E402
from repro.core.tcpjax import TcpLaneResult, run_tcp_lanes  # noqa: E402

JAX_POLS = jax_policies()

FWD_KW = dict(
    lane_params=dict(batch=8, max_batch=8, deschedule_prob=2e-3),
    n_packets=300,
    n_workers=4,
    return_times=True,
)
TCP_KW = dict(
    n_pkts=[40, 40],
    t_start=[0.0, 13.0],
    lane_params=dict(deschedule_prob=2e-3),
    n_workers=4,
)


def _simulated(cls):
    """Every field but ``scan_steps``: that counter is what the engine
    ran (the reference scans every step, a shard its own chunks), not
    what it simulated."""
    return tuple(f for f in cls._fields if f != "scan_steps")


def _assert_scanned_no_more(a, b, ctx):
    """``a`` (compacted or a shard) scanned no more steps than ``b``."""
    x, y = np.asarray(a.scan_steps), np.asarray(b.scan_steps)
    assert (np.asarray(a.active_steps) <= x).all() and (x <= y).all(), ctx


def _assert_results_equal(a, b, fields, ctx):
    for f in fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.shape == y.shape, (ctx, f, x.shape, y.shape)
        np.testing.assert_array_equal(x, y, err_msg=f"{ctx}: field {f}")


# ---------------------------------------------------------------------
# Compacted scan == per-claim scan, bit for bit
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", JAX_POLS)
def test_forwarder_compaction_bit_identical(name):
    compacted = run_lanes(name, np.arange(4), engine="compacted", **FWD_KW)
    reference = run_lanes(name, np.arange(4), engine="reference", **FWD_KW)
    _assert_results_equal(compacted, reference, _simulated(LaneResult), name)
    _assert_scanned_no_more(compacted, reference, name)
    # and the run was actually lossless, so the comparison is not
    # trivially inf == inf everywhere
    assert (np.asarray(compacted.items) == FWD_KW["n_packets"]).all()
    assert (np.asarray(compacted.claimed_prefix) == FWD_KW["n_packets"]).all()


@pytest.mark.parametrize("name", JAX_POLS)
def test_tcp_compaction_bit_identical(name):
    compacted = run_tcp_lanes(name, np.arange(3), engine="compacted", **TCP_KW)
    reference = run_tcp_lanes(name, np.arange(3), engine="reference", **TCP_KW)
    _assert_results_equal(compacted, reference, _simulated(TcpLaneResult), name)
    _assert_scanned_no_more(compacted, reference, name)
    sends = np.asarray(compacted.sends)
    assert np.asarray(compacted.done).all()
    assert (np.asarray(compacted.claimed_popcount) == sends).all()


def test_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown engine"):
        run_lanes("corec", np.arange(2), n_packets=50, engine="warp-drive")


# ---------------------------------------------------------------------
# Fusion: one jitted call over every policy == one call per policy
# ---------------------------------------------------------------------
def test_fused_call_matches_per_policy_calls():
    reqs = [
        dict(policy=p, seeds=np.arange(3), lane_params=FWD_KW["lane_params"])
        for p in JAX_POLS
    ]
    fused = _fused_lanes(
        reqs, n_packets=FWD_KW["n_packets"], n_workers=4, return_times=True
    )
    for p, res in zip(JAX_POLS, fused):
        single = run_lanes(p, np.arange(3), **FWD_KW)
        _assert_results_equal(res, single, LaneResult._fields, p)


def test_fused_timings_report_compile_and_run():
    timings: dict = {}
    reqs = [dict(policy="corec", seeds=np.arange(2))]
    _fused_lanes(reqs, n_packets=100, timings=timings)
    assert timings["compile_s"] > 0 and timings["run_s"] > 0


# ---------------------------------------------------------------------
# Claim budget: a short budget fails loudly, never silently
# ---------------------------------------------------------------------
def test_tight_claim_budget_is_loud():
    # batch=1 needs one claim per packet: a budget of n/4 must leave
    # visible exactly-once violations, not quietly truncated stats
    res = run_lanes(
        "corec",
        np.arange(2),
        lane_params=dict(batch=1),
        n_packets=200,
        claim_budget=50,
        chunk=16,
    )
    assert (np.asarray(res.items) < 200).all()
    assert (np.asarray(res.claimed_popcount) < 200).all()
    assert (np.asarray(res.claimed_prefix) < 200).all()


def test_ample_claim_budget_matches_default():
    # a budget of exactly ceil(n / batch) claims suffices under backlog
    # pressure... but arrivals pace claims, so only the SOUND default
    # (n) is guaranteed: verify the default equals an explicit n budget
    a = run_lanes("corec", np.arange(2), n_packets=150)
    b = run_lanes("corec", np.arange(2), n_packets=150, claim_budget=150)
    _assert_results_equal(a, b, LaneResult._fields, "budget=n")


# ---------------------------------------------------------------------
# Sharded lane axis == unsharded, under 8 forced host devices
# ---------------------------------------------------------------------
_SHARD_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    import jax
    assert jax.local_device_count() == 8, jax.local_device_count()
    from repro.core.jaxplane import LaneResult, run_lanes
    from repro.core.tcpjax import TcpLaneResult, run_tcp_lanes

    kw = dict(
        lane_params=dict(batch=8, max_batch=8, deschedule_prob=1e-3),
        n_packets=200,
        return_times=True,
    )
    # 11 lanes: not a multiple of 8, exercises the per-segment padding
    base = run_lanes("hybrid", np.arange(11), shards=1, **kw)
    shrd = run_lanes("hybrid", np.arange(11), shards=8, **kw)
    for f in LaneResult._fields:
        a, b = np.asarray(getattr(base, f)), np.asarray(getattr(shrd, f))
        if f == "scan_steps":  # each shard runs its own chunks
            assert a.shape == b.shape and (b <= a).all(), f
            continue
        assert a.shape == b.shape and (a == b).all(), f
    auto = run_lanes("corec", np.arange(8), shards="auto", **kw)
    assert (np.asarray(auto.items) == 200).all()

    tbase = run_tcp_lanes("scaleout", np.arange(5), n_pkts=[30, 30], shards=1)
    tshrd = run_tcp_lanes("scaleout", np.arange(5), n_pkts=[30, 30], shards=8)
    for f in TcpLaneResult._fields:
        a, b = np.asarray(getattr(tbase, f)), np.asarray(getattr(tshrd, f))
        if f == "scan_steps":
            assert a.shape == b.shape and (b <= a).all(), f
            continue
        assert a.shape == b.shape and (a == b).all(), f
    print("SHARDED-OK")
    """
)


def test_sharded_equals_unsharded_forced_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src_dir = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARDED-OK" in proc.stdout
