"""Bring-up check of the fused receive sweep on a TPU.

Runs ``repro.core.run_sweep`` through its public entry point at the full
size of the repository's benchmark grids, with all five registry
policies fused into one call per phase and the done-prefix kernel left
on ``prefix_impl="auto"`` (the Pallas kernel on a TPU):

* ``forwarder``: the main grid of ``benchmarks/jax_sweep.py``, 1008
  lanes per policy x 2000 packets;
* ``tcp``: its TCP grid, 2016 lanes per policy, two 128-packet flows;
* ``serving``: the grid of ``benchmarks/serving_sweep.py``, 2016 lanes
  x 1000 sessions per policy (10,080 lanes in one call).

Each phase prints one line with its lane count, compile and run seconds
(the run timed to ``block_until_ready``), the device's peak bytes in use
so far, and whether the compiled program holds a Mosaic kernel
(``tpu_custom_call``).  It fails unless

* every lane meets the exactly-once invariants the benchmarks assert,
* the kernel's prefixes equal ``done_prefix_packed_ref`` computed on the
  same device words, bit for bit,
* the jax plane agrees with the DES reference on the first
  ``DES_SEEDS`` lanes of one grid config per policy, within the parity
  tolerances of ``tests/test_jaxplane.py``, ``tests/test_tcpjax.py`` and
  ``tests/test_servingjax.py``.

``--chips 4`` runs only the lane-sharded path: the serving grid with
``shards=4`` across four chips, compared bit for bit with the same
request at ``shards=1``.

The script stops at once when JAX finds no TPU.  Its last line is one
JSON object naming the device.  Run it from the root of a checkout::

    python chip_smoke.py             # one chip: forwarder, tcp, serving
    python chip_smoke.py --chips 4   # four chips: sharded serving grid
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: parity tolerances of the DES-vs-jax tests (relative error)
P50_RTOL = 0.15
P99_RTOL = 0.35
SLO_RTOL = 0.15
#: lanes per policy (seeds 0..DES_SEEDS-1 of one grid config) compared
#: with the DES reference on the same seeds
DES_SEEDS = 8
#: the grid configs compared with the DES reference
FORWARDER_CFG = dict(batch=8, rate=40.0, deschedule_prob=0.0)
TCP_CFG = dict(batch=32, deschedule_prob=0.0, link_pps=0.85, pkt_budget=1 << 30)
SERVING_CFG = dict(admit_limit=16.0, scale_backlog=12.0, rate=5.0, slo_target=20.0)


def _close(got: float, want: float, rel: float, abs_: float = 0.0) -> bool:
    return abs(got - want) <= max(rel * abs(want), abs_)


def _cfg_lanes(points, cfg: dict) -> np.ndarray:
    """Indices of the grid lanes at ``cfg`` with seed < ``DES_SEEDS``."""
    idx = [
        i
        for i, (c, seed) in enumerate(points)
        if seed < DES_SEEDS and all(c[k] == v for k, v in cfg.items())
    ]
    if len(idx) != DES_SEEDS:
        raise ValueError(f"grid has {len(idx)} lanes at {cfg}, want {DES_SEEDS}")
    return np.asarray(idx)


def _sweep(request):
    """One fused call: the results, its timings and the device's peak
    bytes in use so far (None where the backend does not report it)."""
    import jax

    from repro.core import run_sweep

    timings: dict = {}
    res = run_sweep(request, timings=timings)
    stats = jax.devices()[0].memory_stats() or {}
    return res, timings, stats.get("peak_bytes_in_use")


def _kernel_matches_ref(res, limit, n_bits=None) -> bool:
    """The fused program's done-prefix (the kernel on a TPU) equals the
    pure-jnp reference on the same device words, bit for bit."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ref import done_prefix_packed_ref

    pols = res.policies
    words = jnp.concatenate([res[p].claimed_words for p in pols])
    got = jnp.concatenate([res[p].claimed_prefix for p in pols])
    limit = jnp.broadcast_to(jnp.asarray(limit, jnp.int32), got.shape)
    ref = jax.jit(done_prefix_packed_ref, static_argnames="n_bits")
    return bool(jnp.array_equal(got, ref(words, limit, n_bits=n_bits)))


def _phase(name: str, res, timings: dict, peak, checks: dict) -> dict:
    lanes = sum(int(np.asarray(res[p].claimed_prefix).shape[0]) for p in res.policies)
    return dict(
        phase=name,
        lanes=lanes,
        compile_s=timings["compile_s"],
        run_s=timings["run_s"],
        peak_bytes_in_use=peak,
        tpu_custom_call=timings["mosaic_kernels"] > 0,
        checks=checks,
    )


# ---------------------------------------------------------------------
# DES references on the compared seeds
# ---------------------------------------------------------------------
def _des_forwarder(policy: str, n: int, cfg: dict):
    """Mean per-seed p50 / p99 sojourn of the DES forwarder at ``cfg``
    (Poisson arrivals of 64-byte packets over 256 uniform flows)."""
    from repro.core.forwarder import ForwarderConfig, simulate_forwarder
    from repro.core.traffic import Packet

    p50, p99 = [], []
    for seed in range(DES_SEEDS):
        rng = np.random.default_rng(1000 + seed)
        arr = np.cumsum(rng.exponential(1.0 / cfg["rate"], size=n))
        flows = rng.integers(0, 256, size=n)
        pkts = [
            Packet(seqno=i, flow=int(f), flow_seq=0, size=64, t_arrival=float(t))
            for i, (f, t) in enumerate(zip(flows, arr))
        ]
        fcfg = ForwarderConfig(
            policy=policy,
            batch=cfg["batch"],
            deschedule_prob=cfg["deschedule_prob"],
            seed=seed,
        )
        soj = np.array([t - p.t_arrival for t, p in simulate_forwarder(pkts, fcfg)])
        p50.append(np.percentile(soj, 50))
        p99.append(np.percentile(soj, 99))
    return float(np.mean(p50)), float(np.mean(p99))


def _des_tcp(policy: str, flow_pkts, flow_start, cfg: dict, n_workers: int):
    """Pooled flow completion times of the DES TCP plane at ``cfg``,
    steered by the jax plane's flow hash."""
    from repro.core.jaxplane import rss_hash32
    from repro.core.tcp import TcpSimConfig, simulate_tcp

    hashes = rss_hash32(np.arange(len(flow_pkts)), n_workers)
    flows = [
        (i, int(n), float(t)) for i, (n, t) in enumerate(zip(flow_pkts, flow_start))
    ]
    fct = []
    for seed in range(DES_SEEDS):
        tcfg = TcpSimConfig(
            policy=policy,
            n_workers=n_workers,
            batch=cfg["batch"],
            deschedule_prob=cfg["deschedule_prob"],
            link_pps=cfg["link_pps"],
            seed=seed,
            queue_hints={i: int(h) for i, h in enumerate(hashes)},
        )
        fct += [r.fct for r in simulate_tcp(flows, tcfg)]
    return np.asarray(fct)


def _des_serving(policy: str, request, cfg: dict):
    """Per-seed DES serving results at ``cfg`` on the request's knobs."""
    from repro.core.jaxplane import rss_hash32
    from repro.core.servingjax import ServingSimConfig, simulate_serving_des

    hashes = rss_hash32(np.arange(256), request.n_workers)
    hints = {f: int(h) for f, h in enumerate(hashes)}
    return [
        simulate_serving_des(
            ServingSimConfig(
                policy=policy,
                n_workers=request.n_workers,
                batch=32,
                arrival=request.arrival,
                rate=cfg["rate"],
                capacity=request.n_packets,
                session_alpha=request.traffic_params["session_alpha"],
                admit_limit=cfg["admit_limit"],
                base_workers=request.serving_params["base_workers"],
                scale_backlog=cfg["scale_backlog"],
                slo_target=cfg["slo_target"],
                seed=seed,
                queue_hints=hints,
            )
        )
        for seed in range(DES_SEEDS)
    ]


# ---------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------
def forwarder_phase(n_seeds: int) -> dict:
    from benchmarks.jax_sweep import forwarder_request

    request, points = forwarder_request(n_seeds)
    n = request.n_packets
    res, timings, peak = _sweep(request)
    at = _cfg_lanes(points, FORWARDER_CFG)
    once, des = True, True
    for p in res.policies:
        r = res[p]
        for f in ("claimed_popcount", "claimed_prefix", "items"):
            once &= bool((np.asarray(getattr(r, f)) == n).all())
        j50 = float(np.mean(np.asarray(r.p50)[at]))
        j99 = float(np.mean(np.asarray(r.p99)[at]))
        d50, d99 = _des_forwarder(p, n, FORWARDER_CFG)
        ok = _close(j50, d50, P50_RTOL) and _close(j99, d99, P99_RTOL)
        print(f"  forwarder/{p}: jax p50 {j50} p99 {j99}, des p50 {d50} p99 {d99}")
        des &= bool(ok)
    checks = dict(
        exactly_once=once,
        kernel_eq_ref=_kernel_matches_ref(res, n, n_bits=n),
        des_agree=des,
    )
    return _phase("forwarder", res, timings, peak, checks)


def tcp_phase(n_seeds: int) -> dict:
    from benchmarks.jax_sweep import N_WORKERS, tcp_flows, tcp_request

    request, points = tcp_request(n_seeds)
    flow_pkts, flow_start = tcp_flows()
    res, timings, peak = _sweep(request)
    at = _cfg_lanes(points, TCP_CFG)
    once, des = True, True
    for p in res.policies:
        r = res[p]
        sends = np.asarray(r.sends)
        once &= bool(np.asarray(r.done).all())
        for f in ("claimed_popcount", "claimed_prefix", "items"):
            once &= bool((np.asarray(getattr(r, f)) == sends).all())
        j = np.asarray(r.fct)[at].ravel()
        d = _des_tcp(p, flow_pkts, flow_start, TCP_CFG, N_WORKERS)
        j50, j99 = np.percentile(j, 50), np.percentile(j, 99)
        d50, d99 = np.percentile(d, 50), np.percentile(d, 99)
        ok = _close(j50, d50, P50_RTOL) and _close(j99, d99, P99_RTOL)
        print(f"  tcp/{p}: jax fct p50 {j50} p99 {j99}, des p50 {d50} p99 {d99}")
        des &= bool(ok)
    sends = np.concatenate([np.asarray(res[p].sends) for p in res.policies])
    checks = dict(
        exactly_once=once,
        kernel_eq_ref=_kernel_matches_ref(res, sends),
        des_agree=des,
    )
    return _phase("tcp", res, timings, peak, checks)


def serving_phase(n_seeds: int) -> dict:
    from benchmarks.serving_sweep import serving_request

    request, points = serving_request(n_seeds)
    n = request.n_packets
    res, timings, peak = _sweep(request)
    at = _cfg_lanes(points, SERVING_CFG)
    once, des = True, True
    for p in res.policies:
        r = res[p]
        settled = np.asarray(r.delivered) + np.asarray(r.expired) + np.asarray(r.shed)
        once &= bool((np.asarray(r.claimed_popcount) == settled).all())
        ds = _des_serving(p, request, SERVING_CFG)
        j_slo = float(np.median(np.asarray(r.slo_attained)[at]))
        d_slo = float(np.median([x.slo_attained for x in ds]))
        j_p99 = float(np.median(np.asarray(r.p99)[at]))
        d_p99 = float(np.median([x.p99 for x in ds]))
        j_shed = float(np.median(np.asarray(r.shed)[at]))
        d_shed = float(np.median([x.shed for x in ds]))
        ok = (
            _close(j_slo, d_slo, SLO_RTOL)
            and _close(j_p99, d_p99, P99_RTOL)
            and _close(j_shed, d_shed, 0.5, 10.0)
        )
        print(
            f"  serving/{p}: jax slo {j_slo} p99 {j_p99} shed {j_shed}, "
            f"des slo {d_slo} p99 {d_p99} shed {d_shed}"
        )
        des &= bool(ok)
    checks = dict(
        exactly_once=once,
        kernel_eq_ref=_kernel_matches_ref(res, n, n_bits=n),
        des_agree=des,
    )
    return _phase("serving", res, timings, peak, checks)


def sharded_phase(n_seeds: int, shards: int) -> dict:
    """The serving grid at ``shards`` devices vs one, bit for bit."""
    from benchmarks.serving_sweep import serving_request
    from repro.core.jaxplane import LaneResult

    request, _ = serving_request(n_seeds)
    one, t_one, _ = _sweep(request)
    many, t_many, peak = _sweep(dataclasses.replace(request, shards=shards))
    identical = all(
        np.array_equal(
            np.asarray(getattr(one[p], f)),
            np.asarray(getattr(many[p], f)),
            equal_nan=True,
        )
        for p in one.policies
        # scan_steps is each shard's own chunk count, not a result
        for f in LaneResult._fields
        if f != "scan_steps"
    )
    # where the sharded lanes live: rows of every policy's claim words
    # on each device
    rows: dict = {}
    for p in many.policies:
        for s in many[p].claimed_words.addressable_shards:
            rows[s.device.id] = rows.get(s.device.id, 0) + s.data.shape[0]
    compile_s, run_s = t_one["compile_s"], t_one["run_s"]
    print(f"  sharded: shards=1 compile_s {compile_s} run_s {run_s}")
    print(f"  sharded: lane rows per device {dict(sorted(rows.items()))}")
    checks = dict(
        bit_identical=identical,
        on_all_devices=len(rows) == shards and min(rows.values()) > 0,
    )
    return _phase(f"serving_shards{shards}", many, t_many, peak, checks)


def _device() -> dict:
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    print(f"device: platform={platform} kind={kind} count={len(devs)}")
    return dict(platform=platform, kind=kind, count=len(devs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=(1, 4),
        help="4 = only the lane-sharded serving grid across four chips",
    )
    args = ap.parse_args(argv)
    info = _device()
    platform, count = info["platform"], info["count"]
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {platform})")
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} found {count} chips")

    from benchmarks import jax_sweep, serving_sweep
    from benchmarks.common import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 1:
        phases = [
            lambda: forwarder_phase(jax_sweep.N_SEEDS),
            lambda: tcp_phase(jax_sweep.N_SEEDS),
            lambda: serving_phase(serving_sweep.N_SEEDS),
        ]
    else:
        phases = [lambda: sharded_phase(serving_sweep.N_SEEDS, args.chips)]
    ok = True
    for phase in phases:
        ph = phase()
        checks = dict(ph.pop("checks"), tpu_custom_call=ph["tpu_custom_call"])
        print(json.dumps(dict(ph, **checks)), flush=True)
        ok &= all(checks.values())
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
