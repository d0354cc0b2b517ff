"""Profiler capture and the reduction from trace to per-layer numbers.

``capture`` records a ``jax.profiler`` trace around the traced call;
``load`` turns its ``.xplane.pb`` into plain events: the device's XLA
op events (chip, HLO text cut to ``NAME_CHARS``, start, duration) and
the benchmark's own host spans (``bench.*``), all on the profiler's one
clock.  ``reduce`` works on those plain
events only, so it is tested on a small trace recorded on the chip and
committed under ``bench/tests/data/``.

On a TPU the op line nests: a ``while`` op's event encloses the events
of its body's ops, iteration by iteration, and a ``conditional`` those
of its branch.  The layer of an op follows from that nesting:

* ``kernel``: the done-prefix Pallas kernel (``done_prefix`` in the op's
  own name, the part of its HLO text before `` = ``);
* ``scan``: an op inside a ``while`` event, and the ``while`` itself where
  none of its body runs (loop control);
* ``post_scan``: every other op of the call.

The device profiler stops recording after a few million op events.  A
call that long (the TCP cell's) leaves a trace whose device events end
well before the host's block does; ``reduce`` marks it ``truncated``,
reads busy and idle time over the recorded part only, and gives no
per-call times.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
from contextlib import contextmanager
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"
KERNEL_MARK = "done_prefix"
SCAN_MARK = "%while"
#: the device line of op executions
OP_LINE = "XLA Ops"
NAME_CHARS = 120
#: a trace whose device events end this share of the window before the
#: host's last block does was cut short by the profiler
TRUNCATED_SHARE = 0.1


class UnknownDevice(KeyError):
    """A device kind with no row in ``bench/peaks.json``."""


def device_peaks(kind: str, path: Path = PEAKS) -> dict:
    """Peak rates of ``kind`` from the table; an unknown kind raises."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; known {sorted(table)}")
    return table[kind]


class _Capture:
    path = None  # the .xplane.pb, once the trace has stopped


@contextmanager
def capture(logdir: Path):
    """Trace the body with the Python tracer off; the trace file's path
    is ``.path`` of the yielded object once the body has run."""
    import jax

    logdir = Path(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    cap = _Capture()
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        yield cap
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise RuntimeError(f"profiler wrote no trace under {logdir}")
    cap.path = found[-1]


def load(path) -> dict:
    """Plain events of an ``.xplane.pb``: device ops and the benchmark's
    host spans."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(os.fspath(path))
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    ops.append([plane.name, e.name[:NAME_CHARS],
                                float(e.start_ns), float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        start = float(e.start_ns)
                        spans.append([e.name, start, start + float(e.duration_ns)])
    return {"ops": ops, "spans": spans}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce(events: dict) -> dict:
    """Per-layer numbers of one traced window (times in seconds).

    The window runs from the first ``bench.call`` span's start to the
    last one's end, or, in a truncated trace, to the last recorded op.
    Busy time is the union of op intervals inside it, per chip, averaged
    over chips.  Layer times sum the ops' own time: an event that
    encloses others counts only where none of them runs.  Each idle gap
    is cut at the host spans' edges and attributed to the span it fell
    in: ``bench.block`` (host waiting on the device), ``bench.call``
    outside the block (host work of the call), or none.
    """
    calls = sorted((s, e) for n, s, e in events["spans"] if n == "bench.call")
    blocks = sorted((s, e) for n, s, e in events["spans"] if n == "bench.block")
    if not calls:
        raise ValueError("trace holds no bench.call span")
    lo, hi = calls[0][0], calls[-1][1]
    last_op = max((s + d for _, _, s, d in events["ops"]), default=lo)
    truncated = bool(events["ops"]) and hi - last_op > TRUNCATED_SHARE * (hi - lo)
    if truncated:
        hi = last_op
    chips: dict = {}
    for chip, name, start, dur in events["ops"]:
        chips.setdefault(chip, []).append((start, start + dur, name))
    n_chips = max(len(chips), 1)
    category = {"scan": 0.0, "post_scan": 0.0, "kernel": 0.0}
    op_time: dict = {}
    busy, kernel_events, gaps, per_call = 0.0, 0, [], []
    for k, (chip, evs) in enumerate(sorted(chips.items())):
        merged = _union([[s, e] for s, e, _ in evs])
        busy += _overlap(merged, lo, hi)
        for s, e, name, layer in _own_time(evs):
            dur = max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / n_chips
            if dur > 0:
                kernel_events += layer == "kernel" and s >= lo
                category[layer] += dur
                op_time[name] = op_time.get(name, 0.0) + dur
        if k == 0:
            for s, e in calls:
                e = min(e, hi)
                idle = (e - s) - _overlap(merged, s, e)
                per_call.append({"span_s": (e - s) * 1e-9, "idle_s": idle * 1e-9})
        cur = lo
        for s, e in merged + [[hi, hi]]:
            if min(s, hi) > cur:
                gaps += _pieces(cur, min(s, hi), calls, blocks)
            cur = max(cur, e)
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n_chips * 1e-9,
        "truncated": truncated,
        "n_ops": len(events["ops"]),
        "kernel_events": kernel_events,
        "category_s": category,
        "calls": per_call,
        "top_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[label, dur * 1e-9] for dur, label in gaps[:10]],
    }


def _own_time(evs):
    """Each event's own time, as pieces (start, end, name, layer): the
    parts of it that no event nested inside it covers."""
    evs = sorted(evs, key=lambda x: (x[0], -x[1]))
    out = []
    stack = []  # open events: [start, end, name, layer, covered_until]
    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            _close(stack.pop(), out)
        if stack and e > stack[-1][1]:
            # overlaps its neighbour without nesting: close that first
            _close(stack.pop(), out)
        parent = stack[-1] if stack else None
        if KERNEL_MARK in name.split(" = ")[0]:
            layer = "kernel"
        elif (parent and parent[3] == "scan") or name.startswith(SCAN_MARK):
            layer = "scan"
        else:
            layer = "post_scan"
        if parent:
            if s > parent[4]:
                out.append((parent[4], s, parent[2], parent[3]))
            parent[4] = max(parent[4], e)
        stack.append([s, e, name, layer, s])
    while stack:
        _close(stack.pop(), out)
    return out


def _close(ev, out):
    s, e, name, layer, covered = ev
    if e > covered:
        out.append((covered, e, name, layer))


def _pieces(g_lo: float, g_hi: float, calls, blocks):
    """An idle gap cut at the host spans' edges, each piece labelled by
    the span it lies in: ``bench.block`` (the host waits on the device),
    ``bench.call`` (host work of the call), else ``between calls``."""
    cuts = sorted({g_lo, g_hi, *(t for s, e in calls + blocks for t in (s, e)
                                 if g_lo < t < g_hi)})
    out = []
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        if any(s <= mid < e for s, e in blocks):
            label = "bench.block"
        elif any(s <= mid < e for s, e in calls):
            label = "bench.call"
        else:
            label = "between calls"
        out.append((hi - lo, label))
    return out


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: top device ops and idle gaps."""
    return {
        "device_ops": [[n, s] for n, s in reduced["top_ops"]],
        "idle_gaps": reduced["idle_gaps"],
    }
