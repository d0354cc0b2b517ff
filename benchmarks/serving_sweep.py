"""Million-user open-loop serving sweep on the jax plane, fused.

The serving counterpart of ``jax_sweep.py``: every (admission limit x
autoscale backlog x offered rate x SLO target x seed) lane of every
jax-capable policy runs in ONE fused jitted call through the unified
sweep API (``SweepRequest(scenario="serving")`` ->
:func:`repro.core.run_sweep`).  Each lane is an open-loop scenario —
diurnal nonhomogeneous-Poisson arrivals (by default) driving
heavy-tailed session sizes through the claim-compacted lane engine —
so at the default full size (48 configs x 42 seeds x 5 policies =
10,080 lanes x 1,000 users/lane) one call simulates ~10 million user
sessions, with per-policy SLO attainment computed in-graph.

Per policy the row reports:

* ``slo_attainment`` — delivered-within-target over offered, averaged
  over lanes (the CI floor metric: a serving regression shows up here
  first),
* ``p50_median`` / ``p99_median`` — median per-lane delivered-only
  sojourn percentiles (wedged/empty lanes' infinite percentiles
  excluded and counted),
* ``shed_rate`` — admission-shed sessions over offered (shed-at-claim:
  the overload valve the paper's single-queue driver gets for free
  from batch claims),
* ``undelivered_total`` — sessions stranded in gated workers' queues
  at the horizon (static RSS partitioning's failure mode: scaleout
  strands sub-threshold tails that shared-queue disciplines drain),
* the exactly-once invariant from the packed claim bitmaps
  (``popcount == items + shed`` — shed sessions burn their claim bit).

CI gates ``serving_sweep/<policy>`` rows from
``results/quick/serving_sweep.json``: ``check_regression.py`` fails on
SLO-attainment drops below the baseline floor and p99 regressions.

Skips with a named notice (not a crash) on hosts without jax.
Results land in ``benchmarks/results/serving_sweep.json``.
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
MAX_BATCH = 32
BASE_WORKERS = 2.0

#: the serving grid: 3 x 2 x 4 x 2 = 48 configs; x 42 seeds = 2016
#: lanes/policy, 10,080 lanes over the 5-policy registry in one call
AXES = {
    "admit_limit": [16.0, 48.0, 96.0],
    "scale_backlog": [12.0, 48.0],
    "rate": [2.0, 3.0, 4.0, 5.0],
    "slo_target": [20.0, 40.0],
}
N_SEEDS = 42
CAPACITY = 1000  # users (sessions) generated per lane


def serving_request(
    n_seeds: int = N_SEEDS,
    capacity: int = CAPACITY,
    arrival: str = "diurnal",
    session_alpha: float = 1.8,
    shards: int | str = 1,
):
    """The serving grid as one fused ``SweepRequest`` over every jax
    policy, and the ``(config, seed)`` point of each lane."""
    from repro.core import SweepRequest
    from repro.core.jaxplane import ServingParams, TrafficParams, lane_grid
    from repro.core.policy import jax_policies

    lane_arrays, points = lane_grid(AXES, np.arange(n_seeds))
    seeds = lane_arrays.pop("__seeds__")
    traffic_kw = {k: v for k, v in lane_arrays.items() if k in TrafficParams._fields}
    traffic_kw["session_alpha"] = session_alpha
    serving_kw = {k: v for k, v in lane_arrays.items() if k in ServingParams._fields}
    serving_kw["base_workers"] = BASE_WORKERS
    req = SweepRequest(
        scenario="serving",
        policies=jax_policies(),
        seeds=seeds,
        arrival=arrival,
        traffic_params=traffic_kw,
        serving_params=serving_kw,
        # the grid is the single source of truth for the knobs here;
        # registry presets are for bare run_sweep(scenario="serving")
        use_policy_serving_defaults=False,
        n_packets=capacity,
        n_workers=N_WORKERS,
        max_batch=MAX_BATCH,
        shards=shards,
    )
    return req, points


def run(
    capacity: int = CAPACITY,
    n_seeds: int = N_SEEDS,
    arrival: str = "diurnal",
    session_alpha: float = 1.8,
    lanes_scale: float = 1.0,
    shards: int | str = 1,
):
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - exercised on bare hosts
        notice = f"jax unavailable ({e.__class__.__name__}: {e})"
        emit("serving_sweep/SKIPPED", 0.0, notice)
        return {"skipped": notice}

    from repro.core import run_sweep

    n_seeds = max(1, round(n_seeds * lanes_scale))
    request, points = serving_request(
        n_seeds, capacity, arrival, session_alpha, shards
    )
    pols = list(request.policies)
    lanes = len(request.seeds)
    n_cfg = lanes // n_seeds

    timings: dict = {}
    sweep = run_sweep(request, timings=timings)
    lanes_total = lanes * len(pols)
    compile_s, run_s = timings["compile_s"], timings["run_s"]
    lane_points = lanes_total / run_s
    out: dict = {
        "arrival": arrival,
        "n_workers": N_WORKERS,
        "base_workers": BASE_WORKERS,
        "capacity": int(capacity),
        "session_alpha": session_alpha,
        "lanes_per_policy": int(lanes),
        "axes": {k: list(map(float, v)) for k, v in AXES.items()},
        "n_seeds": int(n_seeds),
        "engine": {
            "fused_policies": len(pols),
            "lanes_total": int(lanes_total),
            "users_total": int(lanes_total) * int(capacity),
            "compile_s": compile_s,
            "run_s": run_s,
            "wall_s": compile_s + run_s,
            "lane_points_per_s": lane_points,
            "users_per_s": int(lanes_total) * int(capacity) / run_s,
            "shards": str(shards),
        },
        "policies": {},
    }
    for pol in pols:
        res = sweep[pol]
        offered = np.asarray(res.offered)
        items = np.asarray(res.items)
        shed = np.asarray(res.shed)
        undel = offered - items - shed
        slo = np.asarray(res.slo_attained)
        p50 = np.asarray(res.p50)
        p99 = np.asarray(res.p99)
        pop = np.asarray(res.claimed_popcount)
        # shed sessions burn their claim bit: exactly-once under admission
        exactly_once = bool((pop == items + shed).all())
        fin = np.isfinite(p99)
        slo_cfg = slo.reshape(n_cfg, n_seeds).mean(axis=1)
        shed_cfg = shed.reshape(n_cfg, n_seeds).sum(axis=1) / np.maximum(
            offered.reshape(n_cfg, n_seeds).sum(axis=1), 1
        )
        configs = []
        for c in range(n_cfg):
            cfg = dict(points[c * n_seeds][0])
            sl = slice(c * n_seeds, (c + 1) * n_seeds)
            blk = p99[sl][np.isfinite(p99[sl])]
            cfg["slo_attainment"] = float(slo_cfg[c])
            cfg["shed_rate"] = float(shed_cfg[c])
            cfg["p99"] = float(np.median(blk)) if blk.size else None
            cfg["undelivered"] = int(undel[sl].sum())
            configs.append(cfg)
        row = {
            "lanes": int(lanes),
            "users": int(lanes) * int(capacity),
            "exactly_once": exactly_once,
            "compile_s": compile_s,
            "run_s": run_s,
            "wall_s": compile_s + run_s,
            "lane_points_per_s": lane_points,
            "slo_attainment": float(slo.mean()),
            "slo_worst_cfg": float(slo_cfg.min()),
            "p50_median": float(np.median(p50[np.isfinite(p50)])),
            "p99_median": float(np.median(p99[fin])),
            "shed_rate": float(shed.sum() / max(offered.sum(), 1)),
            "undelivered_total": int(undel.sum()),
            "wedged_lanes": int((undel > 0).sum()),
            "configs": configs,
        }
        out["policies"][pol] = row
        emit(
            f"serving_sweep/{pol}",
            run_s * 1e6,
            f"{lanes} lanes x {capacity} users (fused x{len(pols)}, "
            f"{lane_points:.0f} lane-points/s, compile {compile_s:.1f}s), "
            f"SLO {row['slo_attainment']:.3f} (worst cfg "
            f"{row['slo_worst_cfg']:.3f}), p99 med {row['p99_median']:.2f}, "
            f"shed {100 * row['shed_rate']:.1f}%, "
            f"undelivered {row['undelivered_total']}",
        )
        if not exactly_once:
            raise AssertionError(
                f"serving_sweep: {pol} violated exactly-once under "
                f"admission (popcount != items + shed)"
            )
    save_json("serving_sweep", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--capacity", type=int, default=CAPACITY)
    ap.add_argument("--n-seeds", type=int, default=N_SEEDS)
    ap.add_argument("--arrival", default="diurnal")
    add_sweep_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(
        capacity=args.capacity,
        n_seeds=args.n_seeds,
        arrival=args.arrival,
        lanes_scale=args.lanes_scale,
        shards=parse_shards(args.shards),
    )


if __name__ == "__main__":
    main()
