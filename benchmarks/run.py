"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see common.emit) and stores
full results under benchmarks/results/.  The dry-run/roofline cells are
produced separately by ``python -m repro.launch.dryrun`` (512-device
placeholder world); ``roofline.run`` here only aggregates their JSON.

``--quick`` runs a smoke-test pass — shrunk packet counts / single rep
for every DES + threaded benchmark plus a shrunk jax-plane sweep,
skipping the heaviest jax modules (kernels / serving / roofline) — and
finishes in a couple of minutes.  ``jax_sweep`` skips itself with a
named notice (no crash) on hosts where jax is unavailable.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from .common import enable_compile_cache, use_quick_results_dir


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="shrunk sizes, skip heaviest jax modules; a couple of minutes",
    )
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.quick:
        # Shrunk-size runs must never overwrite the tracked full-run
        # artifacts under benchmarks/results/.
        use_quick_results_dir()

    # (module name, full kwargs, quick kwargs or None to skip in --quick).
    # Modules import lazily inside the loop so a jax-free host still gets
    # a named per-module failure (or jax_sweep's clean skip) instead of a
    # crash before the first CSV line: kernels_bench / serving_bench /
    # roofline import jax at module top.
    plan = [
        ("ring_ops_bench", {}, dict(n_items=4_096)),  # packed vs per-item ring
        ("queueing_bench", {}, dict(n_jobs=8_000)),  # Figs 3-4
        ("scalability", {}, dict(n_items=1_500, n_jobs=8_000)),  # Tables 2-3
        ("latency_bench", {}, dict(n_jobs=8_000)),  # Figs 5-6
        ("reorder_udp", {}, dict(n_packets=5_000)),  # Fig 7
        ("reorder_traces", {}, dict(n_packets=6_000)),  # Table 4
        ("tcp_flows", {}, dict(scale=30, nflows_list=(32,))),  # Table 5, Figs 8-10
        ("policy_sweep", {}, dict(n_packets=8_000, n_tcp_flows=48)),  # registry
        ("jax_sweep", {}, dict(n_packets=400, tcp_pkts=96)),  # vectorized jax plane
        ("fault_sweep", {}, dict(n_packets=400, n_seeds=3)),  # degraded mode
        ("serving_sweep", {}, dict(capacity=200, n_seeds=2)),  # open-loop serving
        ("overload_sweep", {}, dict(capacity=200, n_seeds=3)),  # retry storms
        ("kernels_bench", {}, None),  # Pallas kernel analytics
        ("serving_bench", {}, None),  # framework-level COREC serving
        ("roofline", {}, None),  # dry-run aggregation (section Roofline)
    ]

    print("name,us_per_call,derived")
    failures = []
    for mod_name, kwargs, quick_kwargs in plan:
        if args.quick:
            if quick_kwargs is None:
                continue
            kwargs = quick_kwargs
        try:
            mod = importlib.import_module(f".{mod_name}", package=__package__)
            if mod_name == "roofline":
                mod.run_all_tags()
            else:
                mod.run(**kwargs)
        except Exception as e:  # noqa: BLE001
            failures.append((mod_name, e))
            traceback.print_exc()
    if failures:
        # Non-zero exit so CI catches a broken benchmark instead of a
        # silently truncated CSV.
        names = ", ".join(f"{name}: {e!r}" for name, e in failures)
        print(f"FAILED ({len(failures)}): {names}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
