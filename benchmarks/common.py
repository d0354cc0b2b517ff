"""Shared benchmark helpers: timing, CSV emission, result storage, and
the sweep-scale CLI flags every fused jax benchmark shares."""

from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Callable, Optional, Union

RESULTS = Path(__file__).resolve().parent / "results"
#: the checkout's fixed compile-cache directory (JAX keys cache entries
#: by path, so a directory that moves between runs never hits)
JAX_CACHE = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compile cache for an entry point.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to the checkout's
    ``.jax_cache/``.  Returns the directory in use, or None on a host
    without jax.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    try:
        import jax
    except ImportError:
        return None
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    return str(JAX_CACHE)


def add_sweep_args(ap, *, quick: bool = False) -> None:
    """Attach the shared fused-sweep flags to an ``argparse`` parser.

    Every jax-plane benchmark (``jax_sweep`` / ``fault_sweep`` /
    ``serving_sweep``) takes the same scale knobs; defining them here
    keeps the flags and help text identical across entry points.
    ``quick`` additionally registers ``--quick`` (shrunk sizes +
    results/quick/ redirect) for benchmarks that support standalone
    smoke runs.
    """
    ap.add_argument(
        "--lanes-scale",
        type=float,
        default=1.0,
        help="multiply the seed axis: lane counts scale linearly with "
        "no extra compiles",
    )
    ap.add_argument(
        "--shards",
        default="1",
        help="partition the lane axis over this many local devices "
        "('auto' = all, incl. --xla_force_host_platform_device_count)",
    )
    if quick:
        ap.add_argument(
            "--quick",
            action="store_true",
            help="shrunk sizes, results under results/quick/",
        )


def parse_shards(value: Union[int, str]) -> Union[int, str]:
    """Normalize a ``--shards`` value: 'auto' stays a string, else int."""
    return value if value == "auto" else int(value)


def use_quick_results_dir() -> Path:
    """Redirect ``save_json`` to results/quick/ for smoke passes.

    ``run.py --quick`` shrinks every benchmark's size, so its JSONs must
    never overwrite the tracked full-run artifacts under results/.
    """
    global RESULTS
    RESULTS = Path(__file__).resolve().parent / "results" / "quick"
    return RESULTS


def timeit(fn: Callable, n: int = 5, warmup: int = 1) -> float:
    """Median wall-time of fn() in microseconds."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def save_json(name: str, obj) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    p = RESULTS / f"{name}.json"
    p.write_text(json.dumps(obj, indent=2, default=str))
    return p
