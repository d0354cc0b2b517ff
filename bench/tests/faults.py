"""Shared driver of the fault tests: one run of a shrunken cell on the
CPU through the harness (the chip look skipped), with a fault planted
in the program underneath."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import manifest  # noqa: E402

_spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)


def small_cell(name: str, **config):
    """The cell at one seed per grid point and two values per axis."""
    cell = manifest.load_cell(name)
    mix = dict(cell.traffic, seeds_per_config=1)
    mix["axes"] = {g: {k: v[:2] for k, v in d.items()} for g, d in mix["axes"].items()}
    return dataclasses.replace(cell, traffic=mix, config=dict(cell.config, **config))


def run_small(monkeypatch, cell, seed: int = 2**31 + 99) -> dict:
    """One harness run of ``cell`` on the CPU; fresh traces, so a fault
    patched into the program is compiled in."""
    monkeypatch.setattr(bench_run, "enable_cache", lambda: None)
    cpu = dict(platform="cpu", kind="cpu", count=1)
    monkeypatch.setattr(bench_run, "device_info", lambda chips: dict(cpu))
    jax.clear_caches()
    try:
        return bench_run.run_cell(cell, seed, 0.2, False)
    finally:
        jax.clear_caches()


def halve_lanes(orig):
    """A lane engine that simulates only the first half of each
    segment's lanes and hands their results back for the second half."""

    def half(requests, **kw):
        requests = list(requests)
        lanes = len(np.asarray(requests[0]["seeds"]))
        keep = lanes // 2

        def cut(v):
            a = np.asarray(v)
            return a[:keep] if a.ndim and a.shape[0] == lanes else v

        small = []
        for req in requests:
            r = dict(req, seeds=np.asarray(req["seeds"])[:keep])
            for key in ("lane_params", "traffic_params", "tcp_params", "fault_params"):
                if key in r and r[key]:
                    r[key] = {k: cut(v) for k, v in r[key].items()}
            small.append(r)
        outs = orig(small, **kw)

        def widen(a):
            a = np.asarray(a)
            return np.concatenate([a, a[: lanes - keep]])

        return [jax.tree_util.tree_map(widen, o) for o in outs]

    return half


def failed(out: dict) -> list:
    return [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
