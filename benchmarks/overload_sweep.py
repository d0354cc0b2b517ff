"""Retry-storm overload sweep: the metastable cliff, fused on the jax
plane.

The overload counterpart of ``serving_sweep.py``: every (retry-policy x
offered rate x response-loss x seed) lane of every jax-capable policy
runs in ONE fused jitted call (retry policies are per-segment
``OverloadConfig`` statics, so the grid drives
:func:`repro.core.jaxplane._fused_lanes` directly with policy x mode
segments).  Three client/server retry policies per Rx policy:

* ``none``     — client timeout only: the healthy baseline goodput.
* ``naive``    — same timeout plus an unconditional retry budget and no
  backoff, admission, or breaker: the no-cancellation worst case.  Every
  request triples the offered load, waits blow past the deadline, and
  goodput collapses — the metastable failure mode of production retry
  storms (served work is all stale, so throughput stays high while
  goodput goes to ~zero).
* ``graceful`` — the registry's per-policy ``overload_defaults`` preset:
  the same retry budget with exponential backoff + jitter, admission
  depth matched to the deadline, and a circuit breaker that browns out
  on a stale queue head.  Degradation is graceful: goodput stays at or
  above the healthy baseline (retries give second chances under
  response loss).

Per policy the row reports ``healthy_goodput`` (mode ``none``),
``naive_goodput_ratio`` / ``graceful_goodput_ratio`` (lane-mean goodput
over the healthy lane's), ``metastable_lanes`` (graceful lanes whose
ratio fell below the 0.5 cliff — the CI 0-invariant), and the extended
exactly-once invariant from the packed claim bitmaps (``popcount ==
delivered + expired + shed``).

CI gates ``overload_sweep/<policy>`` rows from
``results/quick/overload_sweep.json``: ``check_regression.py`` fails on
``graceful_goodput_ratio`` dropping below the baseline floor, any
non-zero ``metastable_lanes``, and ``naive_goodput_ratio`` *rising*
above its (collapsed) baseline band — the cliff disappearing means the
overload model broke.

Skips with a named notice (not a crash) on hosts without jax.
Results land in ``benchmarks/results/overload_sweep.json``.
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
MAX_BATCH = 16

#: client deadline shared by all three modes (units of mean service)
TIMEOUT = 2.0
#: naive mode: the unconditional retry budget with no mitigation
NAIVE_RETRIES = 2
#: a graceful lane below this fraction of healthy goodput is metastable
CLIFF = 0.5

AXES = {
    "rate": [2.0, 3.0],
    "drop_rate": [0.0, 0.1],
}
N_SEEDS = 8
CAPACITY = 400  # requests generated per lane


def _modes(pol: str) -> dict:
    """Retry-policy mode -> overload/admission knob dict for ``pol``."""
    from repro.core.policy import overload_defaults

    return {
        "none": {"timeout": TIMEOUT},
        "naive": {"timeout": TIMEOUT, "retries": NAIVE_RETRIES},
        "graceful": dict(overload_defaults(pol)),
    }


def run(
    capacity: int = CAPACITY,
    n_seeds: int = N_SEEDS,
    lanes_scale: float = 1.0,
    shards: int | str = 1,
):
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - exercised on bare hosts
        notice = f"jax unavailable ({e.__class__.__name__}: {e})"
        emit("overload_sweep/SKIPPED", 0.0, notice)
        return {"skipped": notice}

    from repro.core.jaxplane import _fused_lanes
    from repro.core.policy import jax_policies

    n_seeds = max(1, round(n_seeds * lanes_scale))
    pols = jax_policies()
    rates = AXES["rate"]
    drops = AXES["drop_rate"]
    seeds = np.arange(n_seeds)
    lane_rate = np.repeat(rates, len(drops) * n_seeds).astype(float)
    lane_drop = np.tile(np.repeat(drops, n_seeds), len(rates)).astype(float)
    lane_seeds = np.tile(seeds, len(rates) * len(drops))
    lanes = int(lane_seeds.shape[0])
    n_cfg = lanes // n_seeds

    requests = []
    order = []
    for pol in pols:
        for mode, knobs in _modes(pol).items():
            requests.append(
                dict(
                    policy=pol,
                    seeds=lane_seeds,
                    lane_params={},
                    traffic_params=dict(rate=lane_rate),
                    serving_params=dict(knobs, drop_rate=lane_drop),
                )
            )
            order.append((pol, mode))

    timings: dict = {}
    results = _fused_lanes(
        requests,
        workload="udp",
        service="HT",
        serving=True,
        n_packets=capacity,
        n_workers=N_WORKERS,
        max_batch=MAX_BATCH,
        shards=shards,
        timings=timings,
    )
    by_key = dict(zip(order, results))
    lanes_total = lanes * len(requests)
    compile_s, run_s = timings["compile_s"], timings["run_s"]
    lane_points = lanes_total / run_s
    out: dict = {
        "n_workers": N_WORKERS,
        "capacity": int(capacity),
        "timeout": TIMEOUT,
        "naive_retries": NAIVE_RETRIES,
        "cliff": CLIFF,
        "axes": {k: list(map(float, v)) for k, v in AXES.items()},
        "n_seeds": int(n_seeds),
        "lanes_per_segment": int(lanes),
        "engine": {
            "fused_segments": len(requests),
            "lanes_total": int(lanes_total),
            "compile_s": compile_s,
            "run_s": run_s,
            "wall_s": compile_s + run_s,
            "lane_points_per_s": lane_points,
            "shards": str(shards),
        },
        "policies": {},
    }
    for pol in pols:
        healthy = np.asarray(by_key[(pol, "none")].goodput, dtype=float)
        row: dict = {
            "lanes": int(lanes),
            "healthy_goodput": float(healthy.mean()),
            "lane_points_per_s": lane_points,
            "modes": {},
        }
        for mode in ("none", "naive", "graceful"):
            res = by_key[(pol, mode)]
            good = np.asarray(res.goodput, dtype=float)
            deliv = np.asarray(res.delivered)
            expired = np.asarray(res.expired)
            shed = np.asarray(res.shed)
            pop = np.asarray(res.claimed_popcount)
            # extended exactly-once: every claimed bit is accounted for
            # as a timely delivery, a late/lost (expired) serve, or an
            # admission/breaker shed
            exactly_once = bool((pop == deliv + expired + shed).all())
            ratio = good / np.maximum(healthy, 1.0)
            mrow = {
                "goodput": float(good.mean()),
                "goodput_ratio": float(ratio.mean()),
                "worst_cfg_ratio": float(
                    ratio.reshape(n_cfg, n_seeds).mean(axis=1).min()
                ),
                "dup_served": int(np.asarray(res.dup_served).sum()),
                "expired": int(expired.sum()),
                "shed": int(shed.sum()),
                "exactly_once": exactly_once,
            }
            row["modes"][mode] = mrow
            if not exactly_once:
                raise AssertionError(
                    f"overload_sweep: {pol}/{mode} violated extended "
                    "exactly-once (popcount != delivered + expired + shed)"
                )
        g_ratio = np.asarray(by_key[(pol, "graceful")].goodput, dtype=float)
        g_ratio = g_ratio / np.maximum(healthy, 1.0)
        row["naive_goodput_ratio"] = row["modes"]["naive"]["goodput_ratio"]
        row["graceful_goodput_ratio"] = row["modes"]["graceful"][
            "goodput_ratio"
        ]
        row["metastable_lanes"] = int((g_ratio < CLIFF).sum())
        out["policies"][pol] = row
        emit(
            f"overload_sweep/{pol}",
            run_s * 1e6,
            f"{lanes} lanes x {capacity} reqs x 3 retry modes "
            f"(fused x{len(requests)}, {lane_points:.0f} lane-points/s, "
            f"compile {compile_s:.1f}s), healthy {row['healthy_goodput']:.0f},"
            f" naive ratio {row['naive_goodput_ratio']:.2f}, graceful "
            f"{row['graceful_goodput_ratio']:.2f}, metastable "
            f"{row['metastable_lanes']}",
        )
    save_json("overload_sweep", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--capacity", type=int, default=CAPACITY)
    ap.add_argument("--n-seeds", type=int, default=N_SEEDS)
    add_sweep_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(
        capacity=args.capacity,
        n_seeds=args.n_seeds,
        lanes_scale=args.lanes_scale,
        shards=parse_shards(args.shards),
    )


if __name__ == "__main__":
    main()
