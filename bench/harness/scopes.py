"""The program's own marks in a traced call: name scopes on the device
ops, scan chunks, and host spans.

``repro.core.run_sweep`` marks its work.  Device ops carry name scopes
in their HLO ``op_name`` metadata: ``seg.<policy>`` around each policy
segment; inside it ``scan`` (the chunked scan), ``claims`` (the
forwarder's scatter of claim records) and ``post_scan`` (the outputs);
``chunk.<steps>`` around each chunk of the scan that runs (one
``while`` op of ``<steps>`` steps); and ``done_prefix`` around the
kernel launch.  On the host, ``repro.sweep`` spans the call with
``repro.prepare`` and ``repro.dispatch`` inside it.

A TPU op event carries only its HLO text and times, no metadata; the
metadata is in the HLO modules the profiler stores in the trace's
``/host:metadata`` plane (``ProfileOptions.enable_hlo_proto``, on by
default).  ``load`` joins each op event to its instruction there: the
module is the ``XLA Modules`` event the op runs inside, the instruction
the name before `` = `` in the op's text.  An op with no metadata of its
own (a copy XLA inserted) takes the scope of the op it runs inside.

``reduce`` works on the plain events ``load`` gives, over the window
``trace.reduce`` uses, with the same rule of own time: an event that
encloses others counts only where none of them runs.  A trace of a
program without these marks has no scoped op, chunk or ``repro`` span,
so every number built on them is absent rather than zero.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

from . import trace

#: the benchmark's traces (``bench/run.py`` ``TRACE_DIR``)
TRACE_DIR = Path(__file__).resolve().parent.parent / ".trace"
LAYERS = ("scan", "claims", "post_scan", "done_prefix")
SPAN_PREFIXES = ("bench.", "repro.")
MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
CHUNK_RE = re.compile(r"(?:^|/)chunk\.(\d+)/while$")
CHUNK_SCOPE_RE = re.compile(r"^(seg\.[^/]+)/.*chunk\.(\d+)$")
CHUNK_MARK_RE = re.compile(r"/chunk\.\d+")


# ----------------------------------------------------------------------
# protobuf wire format: just enough to read the stored HLO modules
# ----------------------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of a serialized message;
    a length-delimited value is a ``memoryview`` of its bytes."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i : i + n], i + n
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i : i + width], "little"), i + width
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _first(buf, num, default=b""):
    return next((v for n, v in _fields(buf) if n == num), default)


def _text(v) -> str:
    return bytes(v).decode()


def hlo_op_names(xspace: bytes) -> dict:
    """``{module: {instruction: op_name}}`` of the HLO modules stored in
    a serialized XSpace: XSpace.planes (1) -> XPlane.event_metadata (4)
    -> XEventMetadata.stats (5) -> XStat.bytes_value (6) = HloProto ->
    hlo_module (1) -> computations (3) -> instructions (2) -> name (1),
    metadata (7) -> op_name (2)."""
    out: dict = {}
    for num, plane in _fields(xspace):
        if num != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        for num, entry in _fields(plane):
            if num != 4:
                continue
            meta = _first(entry, 2)
            names = out.setdefault(_text(_first(meta, 2)), {})
            for num, stat in _fields(meta):
                hlo = _first(stat, 6) if num == 5 else b""
                for comp in (v for n, v in _fields(_first(hlo, 1)) if n == 3):
                    for ins in (v for n, v in _fields(comp) if n == 2):
                        op_name = _text(_first(_first(ins, 7), 2))
                        if op_name:
                            names[_text(_first(ins, 1))] = op_name
    return out


def scope_of(op_name: str) -> str:
    """The program's scopes in an ``op_name``: its segment and layer
    (``"seg.corec/scan"``, ``"seg.corec/claims"``, ``"seg.corec"``,
    ``"done_prefix"``, ``""``);
    the ``while`` op of one scan chunk adds ``/chunk.<steps>``.  XLA
    gives an op it merged from several (a fusion across segments, a
    chunk's ``while`` folded into its ``cond``) the names of all, each
    from the root on; their scopes are joined by ``;``, one per segment
    and layer."""
    root = op_name.split("/", 1)[0]
    origins = op_name.split(f"/{root}/") if root else [op_name]
    out: dict = {}  # (segment, layer) -> scope
    for name in origins:
        parts = name.split("/")
        seg = next((p for p in parts if p.startswith("seg.")), None)
        layer = next((p for p in parts if p in LAYERS), None)
        scope = "/".join(p for p in (seg, layer) if p)
        m = CHUNK_RE.search(name)
        if m:
            scope += f"/chunk.{m.group(1)}"
        if scope and (m or (seg, layer) not in out):
            out[(seg, layer)] = scope
    return ";".join(out.values())


def _instruction(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0].lstrip("%")


# ----------------------------------------------------------------------
# load: plain events with scopes
# ----------------------------------------------------------------------
def load(path) -> dict:
    """Plain events of an ``.xplane.pb``: device ops as ``[chip, HLO
    text cut to trace.NAME_CHARS, start, duration, scope]`` and host
    spans ``[name, start, end]`` of ``bench.*`` and ``repro.*``."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    op_names = hlo_op_names(raw)
    prof = ProfileData.from_serialized_xspace(raw)
    del raw
    ops, spans = [], []
    scopes: dict = {}  # (module, op text) -> scope
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace.OP_LINE not in lines:
                continue
            modules = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in (lines[MODULE_LINE].events if MODULE_LINE in lines else ())
            ]
            k = 0
            for e in lines[trace.OP_LINE].events:
                start, name = float(e.start_ns), e.name
                while k + 1 < len(modules) and modules[k + 1][0] <= start:
                    k += 1
                module = modules[k][2] if modules and modules[k][0] <= start else ""
                key = (module, name)
                if key not in scopes:
                    op_name = op_names.get(module, {}).get(_instruction(name), "")
                    scopes[key] = scope_of(op_name)
                ops.append([plane.name, name[: trace.NAME_CHARS], start,
                            float(e.duration_ns), scopes[key]])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        start = float(e.start_ns)
                        spans.append([e.name, start, start + float(e.duration_ns)])
    return {"ops": ops, "spans": spans}


# ----------------------------------------------------------------------
# reduce: time per scope, chunks, spans
# ----------------------------------------------------------------------
def _window(events):
    """``(lo, hi, truncated, last_op_end, calls)`` as ``trace.reduce``
    takes them: the ``bench.call`` spans, cut at the last recorded op
    when the profiler stopped recording early."""
    calls = sorted((s, e) for n, s, e in events["spans"] if n == "bench.call")
    if not calls:
        raise ValueError("trace holds no bench.call span")
    lo, hi = calls[0][0], calls[-1][1]
    last_op = max((o[2] + o[3] for o in events["ops"]), default=lo)
    cut = hi - last_op > trace.TRUNCATED_SHARE * (hi - lo)
    truncated = bool(events["ops"]) and cut
    return lo, (last_op if truncated else hi), truncated, last_op, calls


def _pieces(evs):
    """Own time of each event as pieces ``(start, end, scope)``: where no
    event nested in it runs, the rule of ``trace._own_time``.  An
    unscoped event takes the scope of the event it runs inside (its
    ``chunk.<n>`` mark aside)."""
    evs = sorted(evs, key=lambda x: (x[0], -x[1]))
    out, stack = [], []  # open: [start, end, scope, covered_until]
    for s, e, scope in evs:
        while stack and stack[-1][1] <= s:
            _close(stack.pop(), out)
        if stack and e > stack[-1][1]:
            _close(stack.pop(), out)  # overlaps without nesting
        parent = stack[-1] if stack else None
        if parent:
            if not scope:
                scope = CHUNK_MARK_RE.sub("", parent[2])
            if s > parent[3]:
                out.append((parent[3], s, parent[2]))
            parent[3] = max(parent[3], e)
        stack.append([s, e, scope, s])
    while stack:
        _close(stack.pop(), out)
    return out


def _close(ev, out):
    s, e, scope, covered = ev
    if e > covered:
        out.append((covered, e, scope))


def reduce(events: dict) -> dict:
    """Per-scope numbers of one traced window (times in seconds).

    ``layer_s``: own time of the ops under ``scan``, ``claims``,
    ``post_scan`` and ``done_prefix``, and ``unscoped`` for the ops
    under none of them (a segment's lane set-up, copies XLA inserted
    at the top level); they sum to ``total_s``, the time of all ops,
    which ``trace.reduce``'s ``category_s`` sums too.  An op merged
    from several layers splits its time evenly among them.  Each
    ``chunk.<n>`` ``while`` event recorded whole adds one chunk and
    ``n`` steps to its segment in ``chunks`` / ``steps``, and its
    duration to ``chunk_s`` (shared evenly where one ``while`` runs the
    chunks of several segments).  ``span_s``: the summed duration of
    each ``repro.*`` span inside the calls.  ``idle_s``: the device's
    idle time (first chip) inside the calls, by the innermost host span
    it falls in.  Old op records of four fields count as unscoped.
    """
    lo, hi, truncated, last_op, calls = _window(events)
    chips: dict = {}
    for op in events["ops"]:
        scope = op[4] if len(op) > 4 else ""
        chips.setdefault(op[0], []).append((op[2], op[2] + op[3], scope))
    n_chips = max(len(chips), 1)
    layer_s = dict.fromkeys(LAYERS + ("unscoped",), 0.0)
    chunks: dict = {}
    steps: dict = {}
    chunk_s: dict = {}
    total = 0.0
    idle: dict = {}
    host = [(s, e, n) for n, s, e in events["spans"]]
    for k, (chip, evs) in enumerate(sorted(chips.items())):
        for s, e, scope in _pieces(evs):
            dur = max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / n_chips
            if dur <= 0:
                continue
            total += dur
            origins = scope.split(";")
            for origin in origins:
                parts = origin.split("/") if origin else []
                layer = next((p for p in parts if p in LAYERS), "unscoped")
                layer_s[layer] += dur / len(origins)
        for s, e, scope in evs:
            if "chunk." not in scope or s < lo or e > min(hi, last_op):
                continue
            found = [m for m in map(CHUNK_SCOPE_RE.match, scope.split(";")) if m]
            for m in found:
                seg = m.group(1)
                chunks[seg] = chunks.get(seg, 0) + 1
                steps[seg] = steps.get(seg, 0) + int(m.group(2))
                part = (e - s) * 1e-9 / n_chips / len(found)
                chunk_s[seg] = chunk_s.get(seg, 0.0) + part
        if k == 0:
            merged = trace._union([[s, e] for s, e, _ in evs])
            for c_lo, c_hi in calls:
                cur, c_hi = c_lo, min(c_hi, hi)
                for s, e in merged + [[c_hi, c_hi]]:
                    if min(s, c_hi) > cur:
                        _idle_by_span(cur, min(s, c_hi), host, idle)
                    cur = max(cur, e)
                    if cur >= c_hi:
                        break
    span_s: dict = {}
    for name, s, e in events["spans"]:
        if name.startswith("repro.") and any(c_lo <= s and e <= c_hi
                                             for c_lo, c_hi in calls):
            span_s[name] = span_s.get(name, 0.0) + (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "truncated": truncated,
        "n_calls": len(calls),
        "total_s": total,
        "layer_s": layer_s,
        "chunks": chunks,
        "steps": steps,
        "chunk_s": chunk_s,
        "span_s": span_s,
        "idle_s": idle,
    }


def _idle_by_span(g_lo: float, g_hi: float, host, out: dict) -> None:
    """Add an idle gap to ``out``, cut at the host spans' edges, each
    piece under the innermost span it falls in."""
    cuts = sorted({g_lo, g_hi, *(t for s, e, _ in host for t in (s, e)
                                 if g_lo < t < g_hi)})
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        inside = [(e - s, n) for s, e, n in host if s <= mid < e]
        label = min(inside)[1] if inside else "between calls"
        out[label] = out.get(label, 0.0) + (hi - lo) * 1e-9


# ----------------------------------------------------------------------
# the readers' entry
# ----------------------------------------------------------------------
def latest_trace(logdir: Path = TRACE_DIR):
    found = sorted(glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def of_run(ctx: dict, logdir: Path = TRACE_DIR):
    """The scope reduction of the run's traced call, from the trace the
    run left in ``logdir``, kept in ``ctx`` for the next reader; ``None``
    when there is none, or when its window is not the one
    ``ctx["trace"]`` was reduced over (a trace of another run)."""
    if "scopes" not in ctx:
        path = latest_trace(logdir)
        red = None if path is None else reduce(load(path))
        window = ctx["trace"]["window_s"]
        if red is not None and abs(red["window_s"] - window) > 1e-9 * window:
            red = None
        ctx["scopes"] = red
    return ctx["scopes"]


def layer_ms(ctx: dict, layer: str):
    """Device ms per call under the ``layer`` scopes; none from a trace
    cut short or one without such a scope."""
    red = of_run(ctx)
    if red is None or red["truncated"] or not red["layer_s"][layer]:
        return None
    return 1e3 * red["layer_s"][layer] / red["n_calls"]
