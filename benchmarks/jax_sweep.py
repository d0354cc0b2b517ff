"""Registry-wide vectorized sweep on the jax plane (ONE jit for all).

The payoff of :mod:`repro.core.jaxplane`'s claim-compacted engine:
where ``policy_sweep.py`` evaluates one (policy, config, seed) point
per Python event loop, this benchmark evaluates the whole parameter
grid of EVERY jax-capable policy — claim batch x offered rate x
deschedule probability x seeds, >= 1000 lanes per policy — in a SINGLE
fused jitted call through the unified sweep API
(:func:`repro.core.run_sweep`), with latency percentiles and RFC-4737
reordering computed in-graph and the exactly-once invariant checked
from the packed claim bitmaps (multi-ring done-prefix kernel).

The TCP section does the same for the closed loop
(``SweepRequest(scenario="tcp")``): claim batch x deschedule
probability x sender link rate x per-lane packet budget
(elephant/mice mixes) x seeds, >= 2000 TCP lanes per policy fused
into one call, reporting flow-completion-time p50/p99 and retransmit
counts next to the forwarder latency percentiles.  A second, smaller
SACK leg re-runs the grid's spine under receiver loss — the seeded
random Bernoulli process (``loss_rate``) with one deterministic
drop-once control row (``loss_every``) — to gate the scoreboard
recovery path, the ``sack_undelivered == 0`` delivery invariant, and
the paper's impairment shape (corec FCT p99 within ~3% of scaleout
under random loss).

Compile time is measured separately from steady-state execution
through the AOT lower/compile path: every row reports ``compile_s``
(paid once per fused call) next to ``run_s``, and
``lane_points_per_s`` is steady-state throughput (total fused lanes /
``run_s``) — the metric the CI regression guard gates one-sided.

CLI / ``run()`` knobs: ``--lanes-scale`` multiplies the seed axis
(sweep scale grows linearly in lanes with no new compiles);
``--shards`` partitions the lane axis across local devices via
``jax.shard_map`` over ``repro.compat.lane_mesh`` (``auto`` = every
local device, forced-host CPU devices included).

Skips with a named notice (not a crash) on hosts without jax.

Results land in ``benchmarks/results/jax_sweep.json``.
"""

from __future__ import annotations

import argparse

import numpy as np

from .common import (
    add_sweep_args,
    emit,
    enable_compile_cache,
    parse_shards,
    save_json,
)

N_WORKERS = 4
MAX_BATCH = 64

#: run() ``workload`` values -> SweepRequest arrival processes
ARRIVALS = {"udp": "poisson", "mawi": "bursty", "diurnal": "diurnal"}

#: the sweep grid: 6 x 4 x 3 = 72 configs; x 14 seeds = 1008 lanes/policy
AXES = {
    "batch": [1, 2, 4, 8, 16, 32],
    "rate": [20.0, 30.0, 40.0, 50.0],
    "deschedule_prob": [0.0, 5e-4, 5e-3],
}
N_SEEDS = 14

#: TCP grid: 6 x 3 x 4 x 2 = 144 configs; x 14 seeds = 2016 lanes/policy.
#: ``pkt_budget`` is the per-lane elephant/mice axis: 1<<30 = unbudgeted
#: elephants, 48 = mice lanes that stop after 48 packets per flow.
TCP_AXES = {
    "batch": [1, 2, 4, 8, 16, 32],
    "deschedule_prob": [0.0, 5e-4, 5e-3],
    "link_pps": [0.55, 0.85, 1.1, 1.35],
    "pkt_budget": [1 << 30, 48],
}

#: SACK recovery leg: a smaller grid under receiver loss — gates the
#: scoreboard path and the ``sack_undelivered`` == 0 delivery invariant
#: without doubling the main grid's runtime.  ``loss_rate`` is the
#: random Bernoulli impairment process (seeded, counter-based RNG — the
#: same drop schedule on the DES mirror); the ``loss_rate == 0.0``
#: configs keep the deterministic drop-once control (every 10th segment
#: dropped, the pre-migration regression row).  The deterministic
#: period is chosen to keep the last hole > reorder_thresh segments
#: from the flow tail: tail losses are invisible to FACK (nothing
#: sails past them), so a tail-adjacent period would time every flow
#: out and benchmark the RTO, not the scoreboard.
TCP_SACK_AXES = {
    "batch": [1, 4, 16, 32],
    "deschedule_prob": [0.0, 5e-3],
    "loss_rate": [0.0, 0.03],
}
SACK_LOSS_EVERY = 10
SACK_LINK_PPS = 0.85
#: the paper's robustness claim, CI-gated on the random-loss configs:
#: corec's extra reordering costs <= ~3% FCT p99 vs per-flow-pinned
#: scaleout even under impairment
IMPAIRMENT_P99_BAND = 1.03
#: TCP flow layout of both TCP grids: ``tcp_pkts`` packets split over
#: ``TCP_FLOWS`` flows that start ``TCP_FLOW_GAP`` apart
TCP_FLOWS = 2
TCP_FLOW_GAP = 37.0


def _knobs(lane_arrays: dict, params) -> dict:
    return {k: v for k, v in lane_arrays.items() if k in params._fields}


def tcp_flows(tcp_pkts: int = 256):
    """(per-flow packet counts, per-flow start times) of the TCP grids."""
    pkts = np.full(TCP_FLOWS, max(8, tcp_pkts // TCP_FLOWS), dtype=np.int32)
    return pkts, np.arange(TCP_FLOWS, dtype=np.float32) * TCP_FLOW_GAP


def forwarder_request(
    n_seeds: int = N_SEEDS,
    n_packets: int = 2000,
    workload: str = "udp",
    shards: int | str = 1,
):
    """The main forwarder grid as one fused ``SweepRequest`` over every
    jax policy, and the ``(config, seed)`` point of each lane."""
    from repro.core import SweepRequest
    from repro.core.jaxplane import LaneParams, TrafficParams, lane_grid
    from repro.core.policy import jax_policies

    lane_arrays, points = lane_grid(AXES, np.arange(n_seeds))
    seeds = lane_arrays.pop("__seeds__")
    req = SweepRequest(
        scenario="forwarder",
        policies=jax_policies(),
        seeds=seeds,
        arrival=ARRIVALS[workload],
        lane_params=_knobs(lane_arrays, LaneParams),
        traffic_params=_knobs(lane_arrays, TrafficParams),
        n_packets=n_packets,
        n_workers=N_WORKERS,
        max_batch=MAX_BATCH,
        shards=shards,
    )
    return req, points


def tcp_request(n_seeds: int = N_SEEDS, tcp_pkts: int = 256, shards: int | str = 1):
    """The main TCP grid as one fused ``SweepRequest`` over every jax
    policy, and the ``(config, seed)`` point of each lane."""
    from repro.core import SweepRequest
    from repro.core.jaxplane import LaneParams, lane_grid
    from repro.core.policy import jax_policies
    from repro.core.tcpjax import TcpParams

    lane_arrays, points = lane_grid(TCP_AXES, np.arange(n_seeds))
    seeds = lane_arrays.pop("__seeds__")
    flow_pkts, flow_start = tcp_flows(tcp_pkts)
    req = SweepRequest(
        scenario="tcp",
        policies=jax_policies(),
        seeds=seeds,
        lane_params=_knobs(lane_arrays, LaneParams),
        tcp_params=_knobs(lane_arrays, TcpParams),
        n_packets=flow_pkts,
        t_start=flow_start,
        n_workers=N_WORKERS,
        max_batch=MAX_BATCH,
        shards=shards,
    )
    return req, points


def run(
    n_packets: int = 2000,
    n_seeds: int = N_SEEDS,
    workload: str = "udp",
    tcp_pkts: int = 256,
    lanes_scale: float = 1.0,
    shards: int | str = 1,
):
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - exercised on bare hosts
        notice = f"jax unavailable ({e.__class__.__name__}: {e})"
        emit("jax_sweep/SKIPPED", 0.0, notice)
        return {"skipped": notice}

    from repro.core import SweepRequest, run_sweep
    from repro.core.jaxplane import LaneParams, lane_grid
    from repro.core.tcpjax import TcpParams

    n_seeds = max(1, round(n_seeds * lanes_scale))
    request, points = forwarder_request(n_seeds, n_packets, workload, shards)
    pols = list(request.policies)
    lanes = len(request.seeds)
    n_cfg = lanes // n_seeds

    timings: dict = {}
    sweep = run_sweep(request, timings=timings)
    results = [sweep[p] for p in pols]
    lanes_total = lanes * len(pols)
    compile_s, run_s = timings["compile_s"], timings["run_s"]
    lane_points = lanes_total / run_s
    out: dict = {
        "workload": workload,
        "n_workers": N_WORKERS,
        "n_packets": n_packets,
        "lanes_per_policy": int(lanes),
        "axes": {k: list(map(float, v)) for k, v in AXES.items()},
        "n_seeds": int(n_seeds),
        "engine": {
            "fused_policies": len(pols),
            "lanes_total": int(lanes_total),
            "compile_s": compile_s,
            "run_s": run_s,
            "wall_s": compile_s + run_s,
            "lane_points_per_s": lane_points,
            "shards": str(shards),
        },
        "policies": {},
    }
    for pol, res in zip(pols, results):
        p50 = np.asarray(res.p50)
        p99 = np.asarray(res.p99)
        pop = np.asarray(res.claimed_popcount)
        pref = np.asarray(res.claimed_prefix)
        items = np.asarray(res.items)
        ok_pop = bool((pop == n_packets).all())
        ok_pref = bool((pref == n_packets).all())
        ok_items = bool((items == n_packets).all())
        lossless = ok_pop and ok_pref and ok_items
        # median across seeds within each config -> per-config rows
        p50_cfg = np.median(p50.reshape(n_cfg, n_seeds), axis=1)
        p99_cfg = np.median(p99.reshape(n_cfg, n_seeds), axis=1)
        reorder_cfg = np.median(
            np.asarray(res.reorder_pct).reshape(n_cfg, n_seeds), axis=1
        )
        configs = []
        for c in range(n_cfg):
            cfg = dict(points[c * n_seeds][0])
            cfg["p50"] = float(p50_cfg[c])
            cfg["p99"] = float(p99_cfg[c])
            cfg["reorder_pct"] = float(reorder_cfg[c])
            configs.append(cfg)
        row = {
            "lanes": int(lanes),
            "lossless": lossless,
            "compile_s": compile_s,
            "run_s": run_s,
            "wall_s": compile_s + run_s,
            "lane_points_per_s": lane_points,
            "p50_median": float(np.median(p50)),
            "p99_median": float(np.median(p99)),
            "p99_best": float(p99_cfg.min()),
            "p99_worst": float(p99_cfg.max()),
            "configs": configs,
        }
        out["policies"][pol] = row
        emit(
            f"jax_sweep/{pol}",
            run_s * 1e6,
            f"{lanes} lanes x {n_packets} pkts (fused x{len(pols)}, "
            f"{lane_points:.0f} lane-points/s, compile {compile_s:.1f}s), "
            f"p99 med {row['p99_median']:.3f} best {row['p99_best']:.3f}, "
            f"lossless={lossless}",
        )
        if not lossless:
            raise AssertionError(
                f"jax_sweep: {pol} violated exactly-once "
                f"(popcount/prefix/items mismatch)"
            )

    # ---- closed-loop TCP lanes: FCT percentiles at sweep scale --------
    tcp_req, tcp_points = tcp_request(n_seeds, tcp_pkts, shards)
    t_lanes = len(tcp_req.seeds)
    t_ncfg = t_lanes // n_seeds
    n_flows = TCP_FLOWS
    flow_pkts, flow_start = tcp_flows(tcp_pkts)
    tcp_timings: dict = {}
    tcp_sweep = run_sweep(tcp_req, timings=tcp_timings)
    tcp_results = [tcp_sweep[p] for p in pols]
    t_total = t_lanes * len(pols)
    t_compile, t_run = tcp_timings["compile_s"], tcp_timings["run_s"]
    t_points = t_total / t_run
    out["tcp"] = {
        "lanes_per_policy": int(t_lanes),
        "axes": {k: list(map(float, v)) for k, v in TCP_AXES.items()},
        "n_flows": n_flows,
        "pkts_per_flow": int(flow_pkts[0]),
        "n_seeds": int(n_seeds),
        "engine": {
            "fused_policies": len(pols),
            "lanes_total": int(t_total),
            "compile_s": t_compile,
            "run_s": t_run,
            "wall_s": t_compile + t_run,
            "lane_points_per_s": t_points,
            "shards": str(shards),
        },
        "policies": {},
    }
    for pol, res in zip(pols, tcp_results):
        fct = np.asarray(res.fct)
        done = np.asarray(res.done)
        sends = np.asarray(res.sends)
        ok_pop = bool((np.asarray(res.claimed_popcount) == sends).all())
        ok_pref = bool((np.asarray(res.claimed_prefix) == sends).all())
        ok_items = bool((np.asarray(res.items) == sends).all())
        lossless = ok_pop and ok_pref and ok_items
        complete = bool(done.all())
        retx = np.asarray(res.retransmissions)
        # per-config FCT medians (pooled over seeds and flows)
        fct_cfg = np.median(fct.reshape(t_ncfg, n_seeds * n_flows), axis=1)
        configs = []
        for c in range(t_ncfg):
            cfg = dict(tcp_points[c * n_seeds][0])
            block = fct.reshape(t_ncfg, n_seeds * n_flows)[c]
            cfg["fct_p50"] = float(np.percentile(block, 50))
            cfg["fct_p99"] = float(np.percentile(block, 99))
            cfg["retx_mean"] = float(retx.reshape(t_ncfg, -1)[c].mean())
            configs.append(cfg)
        row = {
            "lanes": int(t_lanes),
            "complete": complete,
            "lossless": lossless,
            "compile_s": t_compile,
            "run_s": t_run,
            "wall_s": t_compile + t_run,
            "lane_points_per_s": t_points,
            "fct_p50": float(np.percentile(fct, 50)),
            "fct_p99": float(np.percentile(fct, 99)),
            "fct_worst": float(fct_cfg.max()),
            "retx_total": int(retx.sum()),
            "retx_per_lane": float(retx.sum() / t_lanes),
            "spurious_total": int(np.asarray(res.spurious).sum()),
            "configs": configs,
        }
        out["tcp"]["policies"][pol] = row
        emit(
            f"jax_sweep/tcp/{pol}",
            t_run * 1e6,
            f"{t_lanes} TCP lanes x {int(flow_pkts.sum())} pkts (fused "
            f"x{len(pols)}, {t_points:.0f} lane-points/s, compile "
            f"{t_compile:.1f}s), FCT p50 {row['fct_p50']:.1f} "
            f"p99 {row['fct_p99']:.1f}, retx/lane {row['retx_per_lane']:.2f}, "
            f"lossless={lossless} complete={complete}",
        )
        if not (lossless and complete):
            raise AssertionError(
                f"jax_sweep/tcp: {pol} violated exactly-once or left "
                f"flows unfinished (lossless={lossless}, complete={complete})"
            )

    # ---- SACK recovery leg: multi-hole loss, delivery invariant -------
    sk_arrays, sk_points = lane_grid(TCP_SACK_AXES, np.arange(n_seeds))
    sk_seeds = sk_arrays.pop("__seeds__")
    s_lanes = sk_seeds.shape[0]
    s_ncfg = s_lanes // n_seeds
    sk_lane_kw = _knobs(sk_arrays, LaneParams)
    sk_tcp_kw = _knobs(sk_arrays, TcpParams)
    sk_tcp_kw["sack"] = True
    sk_tcp_kw["link_pps"] = SACK_LINK_PPS
    # deterministic drop-once control rides the loss_rate == 0 configs
    sk_loss = np.asarray(sk_tcp_kw["loss_rate"], dtype=float)
    sk_tcp_kw["loss_every"] = np.where(
        sk_loss == 0.0, float(SACK_LOSS_EVERY), 0.0
    )
    sack_timings: dict = {}
    sack_sweep = run_sweep(
        SweepRequest(
            scenario="tcp",
            policies=pols,
            seeds=sk_seeds,
            lane_params=sk_lane_kw,
            tcp_params=sk_tcp_kw,
            n_packets=flow_pkts,
            t_start=flow_start,
            n_workers=N_WORKERS,
            max_batch=MAX_BATCH,
            shards=shards,
        ),
        timings=sack_timings,
    )
    s_total = s_lanes * len(pols)
    s_compile, s_run = sack_timings["compile_s"], sack_timings["run_s"]
    s_points_rate = s_total / s_run
    out["tcp_sack"] = {
        "lanes_per_policy": int(s_lanes),
        "axes": {k: list(map(float, v)) for k, v in TCP_SACK_AXES.items()},
        "loss_every": SACK_LOSS_EVERY,
        "link_pps": SACK_LINK_PPS,
        "n_flows": n_flows,
        "pkts_per_flow": int(flow_pkts[0]),
        "n_seeds": int(n_seeds),
        "engine": {
            "fused_policies": len(pols),
            "lanes_total": int(s_total),
            "compile_s": s_compile,
            "run_s": s_run,
            "wall_s": s_compile + s_run,
            "lane_points_per_s": s_points_rate,
            "shards": str(shards),
        },
        "policies": {},
    }
    rand_lanes = sk_loss > 0.0
    for pol in pols:
        res = sack_sweep[pol]
        fct = np.asarray(res.fct)
        done = np.asarray(res.done)
        retx = np.asarray(res.retransmissions)
        delivered = np.asarray(res.delivered)
        # every flow that finished must have delivered its whole payload
        # to the receiver despite the injected holes — the scoreboard's
        # end-to-end reliability invariant, gated at a 0 baseline
        undelivered = int((flow_pkts[None, :] - delivered).sum())
        complete = bool(done.all())
        row = {
            "lanes": int(s_lanes),
            "complete": complete,
            "compile_s": s_compile,
            "run_s": s_run,
            "lane_points_per_s": s_points_rate,
            "fct_p50": float(np.percentile(fct, 50)),
            "fct_p99": float(np.percentile(fct, 99)),
            "fct_p99_random": float(np.percentile(fct[rand_lanes], 99)),
            "fct_p99_control": float(np.percentile(fct[~rand_lanes], 99)),
            "retx_per_lane": float(retx.sum() / s_lanes),
            "spurious_total": int(np.asarray(res.spurious).sum()),
            "sack_undelivered": undelivered,
        }
        out["tcp_sack"]["policies"][pol] = row
        emit(
            f"jax_sweep/tcp_sack/{pol}",
            s_run * 1e6,
            f"{s_lanes} SACK lanes, random loss "
            f"{max(TCP_SACK_AXES['loss_rate']):g} + 1/{SACK_LOSS_EVERY} "
            f"control ({s_points_rate:.0f} lane-points/s), FCT p50 "
            f"{row['fct_p50']:.1f} p99 {row['fct_p99']:.1f} "
            f"(random {row['fct_p99_random']:.1f}), "
            f"retx/lane {row['retx_per_lane']:.2f}, "
            f"undelivered={undelivered} complete={complete}",
        )
        if undelivered or not complete:
            raise AssertionError(
                f"jax_sweep/tcp_sack: {pol} left data undelivered under "
                f"loss (undelivered={undelivered}, complete={complete})"
            )
    # The paper's impairment shape on the fused random-loss grid: the
    # shared queue's extra reordering costs corec at most ~3% of FCT
    # p99 vs per-flow-pinned scaleout at loss_rate <= 0.03 — the same
    # seeded drop schedule hits both policies, so the ratio isolates
    # the policy effect.
    p99_corec = out["tcp_sack"]["policies"]["corec"]["fct_p99_random"]
    p99_scale = out["tcp_sack"]["policies"]["scaleout"]["fct_p99_random"]
    shape_ratio = p99_corec / p99_scale
    out["tcp_sack"]["impairment"] = {
        "loss_rate": float(max(TCP_SACK_AXES["loss_rate"])),
        "corec_p99": p99_corec,
        "scaleout_p99": p99_scale,
        "p99_ratio": float(shape_ratio),
        "band": IMPAIRMENT_P99_BAND,
    }
    if not shape_ratio <= IMPAIRMENT_P99_BAND:
        raise AssertionError(
            f"jax_sweep/tcp_sack: corec FCT p99 {p99_corec:.2f} exceeds "
            f"{IMPAIRMENT_P99_BAND:g}x scaleout {p99_scale:.2f} under "
            f"random loss (ratio {shape_ratio:.3f}) — the paper's "
            "impairment shape regressed"
        )
    save_json("jax_sweep", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-packets", type=int, default=2000)
    ap.add_argument("--n-seeds", type=int, default=N_SEEDS)
    ap.add_argument("--workload", default="udp")
    ap.add_argument("--tcp-pkts", type=int, default=256)
    add_sweep_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    shards = parse_shards(args.shards)
    run(
        n_packets=args.n_packets,
        n_seeds=args.n_seeds,
        workload=args.workload,
        tcp_pkts=args.tcp_pkts,
        lanes_scale=args.lanes_scale,
        shards=shards,
    )


if __name__ == "__main__":
    main()
