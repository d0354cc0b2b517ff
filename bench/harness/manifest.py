"""``BENCHMARK.json`` and the data files it names, found by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); its correctness limits live
in ``bench/limits/<cell>.json``, each per-layer metric is a reader
``bench/metrics/<metric>.py``, and each scenario a configuration names
is ``bench/scenarios/<scenario>.py`` (its knob groups, offered packets,
guarantees and plain reference).  Adding a cell, configuration, mix,
metric or scenario adds files and entries; no file here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class ManifestError(ValueError):
    """A manifest or data file that breaks the benchmark's rules."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what} {name!r}: want 1-64 of letters, digits, '_', '.', '-' "
            "starting with a letter, digit or '_'"
        )
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"unit of {what} {unit!r}: want 1-16 of letters, digits, "
            "'_', '/', '%', '.', '-'"
        )
    return unit


def _load_json(path: Path, what: str) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"{what}: no file {path}") from None


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its data files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


def load_manifest(root: Path = ROOT) -> dict:
    """Read and validate ``BENCHMARK.json`` (names, units, references)."""
    man = _load_json(root / "BENCHMARK.json", "manifest")
    seen: dict = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in man.get(kind, []):
            name = check_name(entry.get("name"), kind)
            group = "metric" if kind in ("end_to_end", "per_layer") else kind
            if (group, name) in seen:
                raise ManifestError(f"duplicate {group} name {name!r}")
            seen[(group, name)] = entry
            if kind in ("end_to_end", "per_layer"):
                check_unit(entry.get("unit"), name)
                if entry.get("better") not in ("lower", "higher"):
                    raise ManifestError(f"{name}: better must be lower or higher")
    configs = {c["name"] for c in man["configs"]}
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        cell, config = w["name"], check_name(w["config"], "config")
        check_name(w["traffic"], "traffic")
        if config not in configs:
            raise ManifestError(f"cell {cell}: unknown config {config!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {cell}: chips must be 1 or 4")
    for c in man["configs"]:
        for key in c.get("reduced", []):
            check_name(key, "reduced key")
    for m in man["end_to_end"] + man["per_layer"]:
        name = m["name"]
        for cell in m.get("workloads", []):
            if cell not in cells:
                raise ManifestError(f"metric {name}: unknown cell {cell!r}")
        if m in man["per_layer"] and m.get("moves") not in e2e:
            moves = m.get("moves")
            raise ManifestError(f"metric {name}: moves unknown {moves!r}")
    return man


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and limits."""
    man = load_manifest(root)
    by_name = {w["name"]: w for w in man["workloads"]}
    if name not in by_name:
        raise ManifestError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    bench = root / "bench"
    cfg_entry = next(c for c in man["configs"] if c["name"] == w["config"])
    mix = w["traffic"]
    config = _load_json(root / cfg_entry["file"], "config " + w["config"])
    traffic = _load_json(bench / "traffic" / f"{mix}.json", "traffic")
    limits = _load_json(bench / "limits" / f"{name}.json", "limits")
    scenarios = (traffic.get("scenario"), config.get("scenario"))
    scenario(scenarios[1], bench)
    if scenarios[0] != scenarios[1]:
        raise ManifestError(
            f"cell {name}: mix {mix} is for scenario {scenarios[0]!r}, "
            f"config for {scenarios[1]!r}"
        )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in man["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in man["per_layer"] if _reports(m, name)),
    )


_MODULES: dict = {}


def _module(kind: str, name: str, bench: Path):
    """``bench/<kind>/<name>.py``, loaded once."""
    path = bench / kind / (check_name(name, kind) + ".py")
    if path not in _MODULES:
        if not path.exists():
            raise ManifestError(f"{kind} {name!r}: no file {path}")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, bench).read


def scenario(name: str, bench: Path = BENCH):
    """The scenario module ``bench/scenarios/<name>.py``; an unknown
    scenario raises rather than falling into another's rules."""
    if name is None:
        raise ManifestError("configuration names no scenario")
    return _module("scenarios", name, bench)
