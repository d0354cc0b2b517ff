"""Readings that a cell's correctness limits are set from.

    python bench/control.py --workload <cell> --seeds 12 --control-seeds 3

For each run seed, in one process on the chip: the cell's request is
run once through ``run_sweep`` at the cell's own size, and every number
the benchmark compares is read twice: with the program's results (the
lower reading: sound runs), and, on the first ``--control-seeds`` seeds,
with the plain reference computed in bfloat16 put in the program's place
for the sampled lanes (the control, which has to fail).  One JSON line
per seed goes to standard output and to ``chiprun_out/control_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from harness import correct, manifest, sweep  # noqa: E402


def readings(cell, seed: int, control: bool) -> dict:
    import jax
    import ml_dtypes

    from repro.core import run_sweep

    built = sweep.build(cell.config, cell.traffic, seed)
    t0 = time.perf_counter()
    res = run_sweep(built.request)
    jax.block_until_ready(res.lanes)
    call_s = time.perf_counter() - t0
    picks = correct.sample_lanes(
        res, built.lanes_per_policy, correct.SAMPLE_PER_POLICY, seed
    )
    t0 = time.perf_counter()
    ref = correct.reference_stats(built, cell.config, cell.traffic, picks)
    ref_s = time.perf_counter() - t0
    out = dict(
        seed=seed,
        call_s=call_s,
        reference_s=ref_s,
        sound=correct.numbers(built, res, cell.config, cell.traffic, seed, ref=ref),
    )
    if control:
        low = correct.reference_stats(
            built, cell.config, cell.traffic, picks, dtype=ml_dtypes.bfloat16
        )
        out["control"] = correct.numbers(
            built, res, cell.config, cell.traffic, seed, ref=ref, got=low
        )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    import run as bench_run

    bench_run.device_info(cell.chips)
    bench_run.enable_cache()
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / f"control_{cell.name}.jsonl", "a") as f:
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            line = readings(cell, seed, k < args.control_seeds)
            print(json.dumps(line)[:300], flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
