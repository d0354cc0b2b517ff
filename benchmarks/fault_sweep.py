"""Degraded-mode sweep: crash time x straggler factor x policy, fused.

The robustness counterpart of ``jax_sweep.py``: every (crash-time,
straggler-factor, seed) lane of every jax-capable policy runs in ONE
fused jitted call on the claim-compacted engine (via
:func:`repro.core.run_sweep`) with the fault plane armed — worker 1
crashes at ``crash_t`` (its in-flight batch strands and, after the
claim ``lease`` expires, a live worker reclaims the remainder), worker
0 runs ``straggler`` x slower.  Each policy row
reports the paper-style health metrics next to the recovery ones:

* ``healthy_p99`` / ``degraded_p99`` — median per-lane p99 sojourn on
  the fault-free configs vs the faulted ones (wedged lanes' infinite
  percentiles are excluded and counted separately),
* ``recovery_median`` / ``recovery_worst`` — time from the crash to
  the last delivery (``drain_t - crash_t``) over crashed lanes that
  drained: the lease timeout plus the re-served remainder,
* ``duplicates_per_fault`` — re-delivered items per crashed lane
  (at-least-once accounting; bounded by one batch per fault),
* ``reclaimed_mean`` — items recovered through lease reclamation,
* ``wedged_lanes`` — lanes that ended with undelivered items.  Zero
  for every lease-capable policy; ``locked`` opts out of leases
  (``supports_leases=False``) so its mid-claim crashes wedge the
  shared queue behind the dead lock holder — reported, not hung (the
  compacted scan's ``halted`` flag stops paying the claim budget).

CI gates the degraded rows: ``check_regression.py`` reads
``fault_sweep/<policy>`` from ``results/quick/fault_sweep.json`` and
fails on p99 regressions, duplicate-count growth, or a lease-capable
policy wedging at all.

Skips with a named notice (not a crash) on hosts without jax.
Results land in ``benchmarks/results/fault_sweep.json``.
"""

from __future__ import annotations

import argparse
import math

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
CRASH_WORKER = 1
STRAGGLER_WORKER = 0

#: the fault grid: None = no crash; 4 x 3 = 12 configs per policy
CRASH_TS = [None, 2.0, 4.0, 8.0]
STRAGGLERS = [1.0, 3.0, 6.0]
N_SEEDS = 8


def run(
    n_packets: int = 2000,
    n_seeds: int = N_SEEDS,
    lease: float = 3.0,
    workload: str = "udp",
    lanes_scale: float = 1.0,
    shards: int | str = 1,
):
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - exercised on bare hosts
        notice = f"jax unavailable ({e.__class__.__name__}: {e})"
        emit("fault_sweep/SKIPPED", 0.0, notice)
        return {"skipped": notice}

    from repro.core import SweepRequest, run_sweep
    from repro.core.policy import get_spec, jax_policies

    n_seeds = max(1, round(n_seeds * lanes_scale))
    pols = jax_policies()
    configs = [(ct, sf) for ct in CRASH_TS for sf in STRAGGLERS]
    n_cfg = len(configs)
    seeds = np.tile(np.arange(n_seeds, dtype=np.uint32), n_cfg)
    crash_arr = np.repeat(
        [math.inf if ct is None else float(ct) for ct, _ in configs], n_seeds
    ).astype(np.float32)
    slow_arr = np.repeat([sf for _, sf in configs], n_seeds).astype(np.float32)
    fault_kw = dict(
        crash_t=crash_arr,
        straggler=slow_arr,
        crash_worker=float(CRASH_WORKER),
        straggler_worker=float(STRAGGLER_WORKER),
        lease=float(lease),
    )
    timings: dict = {}
    sweep = run_sweep(
        SweepRequest(
            scenario="forwarder",
            policies=pols,
            seeds=seeds,
            arrival={"udp": "poisson", "mawi": "bursty"}.get(workload, workload),
            service="fwd",
            fault_params=fault_kw,
            n_packets=n_packets,
            n_workers=N_WORKERS,
            max_batch=MAX_BATCH,
            shards=shards,
        ),
        timings=timings,
    )
    results = [sweep[p] for p in pols]
    lanes = seeds.shape[0]
    compile_s, run_s = timings["compile_s"], timings["run_s"]
    lane_points = lanes * len(pols) / run_s
    out: dict = {
        "workload": workload,
        "n_workers": N_WORKERS,
        "n_packets": n_packets,
        "lease": float(lease),
        "crash_worker": CRASH_WORKER,
        "straggler_worker": STRAGGLER_WORKER,
        "axes": {
            "crash_t": [ct for ct, _ in configs[:: len(STRAGGLERS)]],
            "straggler": list(STRAGGLERS),
        },
        "n_seeds": int(n_seeds),
        "engine": {
            "fused_policies": len(pols),
            "lanes_total": int(lanes * len(pols)),
            "compile_s": compile_s,
            "run_s": run_s,
            "lane_points_per_s": lane_points,
        },
        "policies": {},
    }
    crashed_mask = np.isfinite(crash_arr)
    healthy_mask = ~crashed_mask & (slow_arr == 1.0)
    for pol, res in zip(pols, results):
        p99 = np.asarray(res.p99)
        drain = np.asarray(res.drain_t)
        dups = np.asarray(res.duplicates)
        recl = np.asarray(res.reclaimed)
        undel = np.asarray(res.undelivered)
        wedged = undel > 0
        drained_crash = crashed_mask & ~wedged
        recovery = drain[drained_crash] - crash_arr[drained_crash]
        finite_deg = p99[~healthy_mask & np.isfinite(p99)]
        per_cfg = []
        for c, (ct, sf) in enumerate(configs):
            sl = slice(c * n_seeds, (c + 1) * n_seeds)
            row = {
                "crash_t": ct,
                "straggler": sf,
                "p99_median": float(np.median(p99[sl][np.isfinite(p99[sl])]))
                if np.isfinite(p99[sl]).any()
                else None,
                "duplicates_mean": float(dups[sl].mean()),
                "reclaimed_mean": float(recl[sl].mean()),
                "wedged": int(wedged[sl].sum()),
            }
            if ct is not None and (~wedged[sl]).any():
                row["recovery_median"] = float(
                    np.median(drain[sl][~wedged[sl]] - float(ct))
                )
            per_cfg.append(row)
        n_crashed = int(crashed_mask.sum())
        row = {
            "lanes": int(lanes),
            "supports_leases": bool(get_spec(pol).leases),
            "healthy_p99": float(np.median(p99[healthy_mask])),
            "degraded_p99": float(np.median(finite_deg)),
            "recovery_median": float(np.median(recovery))
            if recovery.size
            else None,
            "recovery_worst": float(recovery.max()) if recovery.size else None,
            "duplicates_per_fault": float(dups[crashed_mask].sum() / n_crashed),
            "reclaimed_mean": float(recl[crashed_mask].mean()),
            "wedged_lanes": int(wedged.sum()),
            "undelivered_total": int(undel.sum()),
            "configs": per_cfg,
        }
        out["policies"][pol] = row
        rec = (
            f"recovery med {row['recovery_median']:.2f}"
            if row["recovery_median"] is not None
            else "recovery n/a"
        )
        emit(
            f"fault_sweep/{pol}",
            run_s * 1e6,
            f"{lanes} lanes x {n_packets} pkts, p99 {row['healthy_p99']:.3f}"
            f"->{row['degraded_p99']:.3f}, {rec}, "
            f"dups/fault {row['duplicates_per_fault']:.2f}, "
            f"wedged {row['wedged_lanes']}",
        )
        if get_spec(pol).leases and row["wedged_lanes"]:
            raise AssertionError(
                f"fault_sweep: lease-capable policy {pol!r} wedged "
                f"{row['wedged_lanes']} lanes (lease reclamation failed)"
            )
        if not get_spec(pol).leases and not wedged[crashed_mask].any():
            raise AssertionError(
                f"fault_sweep: {pol!r} has no lease yet never wedged — "
                "the no-recovery control lost its fault"
            )
    save_json("fault_sweep", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-packets", type=int, default=2000)
    ap.add_argument("--n-seeds", type=int, default=N_SEEDS)
    ap.add_argument("--lease", type=float, default=3.0)
    ap.add_argument("--workload", default="udp")
    add_sweep_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(
        n_packets=args.n_packets,
        n_seeds=args.n_seeds,
        lease=args.lease,
        workload=args.workload,
        lanes_scale=args.lanes_scale,
        shards=parse_shards(args.shards),
    )


if __name__ == "__main__":
    main()
