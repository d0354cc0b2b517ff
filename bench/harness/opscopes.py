"""Device time under a name scope found anywhere in an op's ``op_name``.

``harness.scopes`` splits a traced call by its fixed layers (``scan``,
``claims``, ``post_scan``, ``done_prefix``) and keeps only each op's
segment and layer.  Two of the program's scopes sit elsewhere:
``serve`` around the serving branches of the claim step (the autoscale
wake gate and shed-at-claim admission, inside the scan, under the
step's ``vmap``, so the name reads ``vmap(serve)``) and ``lane_setup``
around each segment's lane set-up (traffic generation and queue views).
This module joins each op event to its whole ``op_name`` from the HLO
modules the trace stores, as ``scopes.load`` does, and sums own time
(``scopes`` rule: an event that encloses others counts only where none
of them runs; an op with no metadata takes its enclosing op's name).

A trace of a program without these scopes has no such op, so its
reading is absent rather than zero.
"""

from __future__ import annotations

import re
from pathlib import Path

from . import scopes, trace

NAMES = ("serve", "lane_setup")


def _pattern(name: str):
    # the scope bare or inside transform names: "serve", "vmap(serve)"
    return re.compile(r"(?:^|/)(?:[\w.]+\()*" + re.escape(name) + r"\)*(?:/|$)")


PATTERNS = {name: _pattern(name) for name in NAMES}


def share(op_name: str, name: str) -> float:
    """Share of an op's time under scope ``name``: the share of its
    origins whose name holds the scope (XLA names an op it merged from
    several by all of them, each from the root on)."""
    if not op_name:
        return 0.0
    root = op_name.split("/", 1)[0]
    origins = op_name.split(f"/{root}/") if root else [op_name]
    hits = sum(1 for o in origins if PATTERNS[name].search(o))
    return hits / len(origins)


def load(path) -> dict:
    """Plain events of an ``.xplane.pb``: device ops as ``[chip, HLO text
    cut to trace.NAME_CHARS, start, duration, op_name]`` and the
    benchmark's ``bench.call`` spans ``[name, start, end]``."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    op_names = scopes.hlo_op_names(raw)
    prof = ProfileData.from_serialized_xspace(raw)
    del raw
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace.OP_LINE not in lines:
                continue
            mods = lines.get(scopes.MODULE_LINE)
            modules = [(e.start_ns, e.name) for e in (mods.events if mods else ())]
            k = 0
            for e in lines[trace.OP_LINE].events:
                start = float(e.start_ns)
                while k + 1 < len(modules) and modules[k + 1][0] <= start:
                    k += 1
                module = modules[k][1] if modules and modules[k][0] <= start else ""
                op_name = op_names.get(module, {}).get(scopes._instruction(e.name), "")
                ops.append([plane.name, e.name[: trace.NAME_CHARS], start,
                            float(e.duration_ns), op_name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.call":
                        start = float(e.start_ns)
                        spans.append([e.name, start, start + float(e.duration_ns)])
    return {"ops": ops, "spans": spans}


def reduce(events: dict) -> dict:
    """Seconds of own device time under each of ``NAMES`` in the traced
    window (``scopes`` window and rule of own time, averaged over
    chips), with ``n_calls``, ``window_s`` and ``truncated``."""
    lo, hi, truncated, _, calls = scopes._window(events)
    chips: dict = {}
    for chip, _, start, dur, op_name in events["ops"]:
        chips.setdefault(chip, []).append((start, start + dur, op_name))
    n_chips = max(len(chips), 1)
    scope_s = dict.fromkeys(NAMES, 0.0)
    for evs in chips.values():
        for s, e, op_name in scopes._pieces(evs):
            dur = max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / n_chips
            for name in NAMES:
                scope_s[name] += dur * share(op_name, name)
    return {
        "scope_s": scope_s,
        "n_calls": len(calls),
        "window_s": (hi - lo) * 1e-9,
        "truncated": truncated,
    }


def of_run(ctx: dict, logdir: Path = scopes.TRACE_DIR):
    """The reduction of the run's traced call, kept in ``ctx`` for the
    next reader; ``None`` without a trace of this run's window."""
    if "opscopes" not in ctx:
        path = scopes.latest_trace(logdir)
        red = None if path is None else reduce(load(path))
        window = ctx["trace"]["window_s"]
        if red is not None and abs(red["window_s"] - window) > 1e-9 * window:
            red = None
        ctx["opscopes"] = red
    return ctx["opscopes"]


def scope_ms(ctx: dict, name: str):
    """Device ms per call under scope ``name``; none from a trace cut
    short or one with no op under it."""
    red = of_run(ctx)
    if red is None or red["truncated"] or not red["scope_s"][name]:
        return None
    return 1e3 * red["scope_s"][name] / red["n_calls"]
