"""The fused sweep's own record: set-up phases of every program this
process compiled, and the scan counters of the last sweep.

Set-up is read from JAX's own monitoring events, so it costs nothing
beyond the listeners and reading it needs no device sync.  Each
compiled program has three phases, each a span JAX reports with the
program's name:

* ``trace_s``: ``/jax/core/compile/jaxpr_trace_duration``, Python to
  jaxpr;
* ``lower_s``: ``/jax/core/compile/jaxpr_to_mlir_module_duration``,
  jaxpr to the MLIR module;
* ``load_s``: ``/jax/core/compile/backend_compile_duration``, the XLA
  compile, or with a persistent compile cache the cache key, the
  lookup and, on a hit, the load of the stored executable.

A span that starts while another of these is open in the same thread
(the ``eval_shape`` and inner ``jit`` traces inside a program's trace,
the primitives traced while lowering) counts inside the open one and
is not added again.  The persistent cache's events
(``/jax/compilation_cache/cache_hits``, ``cache_misses``,
``compile_requests_use_cache`` and ``cache_retrieval_time_sec``) go to
the program whose compile span is open.

Programs are keyed by the name JAX gives the traced function
(``_run_fused_impl``, ``_run_tcp_fused_impl``); the lowering and compile
events' ``jit(<name>)`` maps onto it.  :func:`install` registers the
listeners once per process (again if something cleared them); the
fused entry points call it before they trace, so after any
``run_sweep``::

    from repro.core import record
    record.program("_run_fused_impl")
    # Phases(name='_run_fused_impl', compiles=1, trace_s=3.9, lower_s=1.1,
    #        load_s=2.4, cache='hit', retrieval_s=1.2)

:func:`last_sweep` gives the ``active_steps`` / ``scan_steps`` counters
of the last ``run_sweep`` call, per policy, as the arrays it returned.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, replace

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_PHASE_FIELD = {TRACE: "trace_s", LOWER: "lower_s", COMPILE: "load_s"}


@dataclass
class Phases:
    """Set-up of one program, summed over its compiles in this process.

    ``cache`` is the persistent cache's answer to the last compile:
    ``"hit"``, ``"miss"``, or ``None`` when no cache was in use.
    """

    name: str
    compiles: int = 0
    trace_s: float = 0.0
    lower_s: float = 0.0
    load_s: float = 0.0
    cache: str | None = None
    retrieval_s: float = 0.0


_lock = threading.Lock()
_programs: dict = {}  # name -> Phases
_open = threading.local()  # .stack: [(event, name)] of open phase spans
_last_sweep: dict = {}


def _program_name(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


def _phases(name: str) -> Phases:
    if name not in _programs:
        _programs[name] = Phases(name)
    return _programs[name]


def _on_enter(event, _start, fun_name="", **_):
    # JAX records the start of each phase span as a scalar
    if event not in _PHASE_FIELD:
        return
    stack, name = _stack(), _program_name(fun_name)
    if event == COMPILE and not stack:
        with _lock:
            _phases(name).cache = None
    stack.append((event, name))


def _on_span(event, start, end, fun_name="", **_):
    if event not in _PHASE_FIELD:
        return
    stack, name = _stack(), _program_name(fun_name)
    if (event, name) in stack:
        del stack[len(stack) - 1 - stack[::-1].index((event, name)) :]
    if stack:
        return  # nested in an open phase span: counted there
    field = _PHASE_FIELD[event]
    with _lock:
        p = _phases(name)
        setattr(p, field, getattr(p, field) + (end - start))
        p.compiles += event == COMPILE


def _compiling() -> str | None:
    """The program whose compile span is open in this thread."""
    return next((n for e, n in reversed(_stack()) if e == COMPILE), None)


def _on_event(event, **_):
    name = _compiling()
    if name is None or event not in (CACHE_USED, CACHE_HIT, CACHE_MISS):
        return
    if event == CACHE_USED:
        import jax

        # JAX computes a cache key even with no cache directory set
        if not jax.config.jax_compilation_cache_dir:
            return
    with _lock:
        _phases(name).cache = "hit" if event == CACHE_HIT else "miss"


def _on_duration(event, secs, **_):
    name = _compiling()
    if event == CACHE_RETRIEVAL and name is not None:
        with _lock:
            _phases(name).retrieval_s += secs


def install() -> None:
    """Register the listeners, each exactly once, also where something
    cleared or already registered them."""
    import jax.monitoring as mon

    for listener, unregister, register in (
        (_on_enter, mon.unregister_scalar_listener, mon.register_scalar_listener),
        (
            _on_span,
            mon.unregister_event_time_span_listener,
            mon.register_event_time_span_listener,
        ),
        (_on_event, mon.unregister_event_listener, mon.register_event_listener),
        (
            _on_duration,
            mon.unregister_event_duration_listener,
            mon.register_event_duration_secs_listener,
        ),
    ):
        try:
            unregister(listener)
        except (AssertionError, ValueError):
            pass  # not registered
        register(listener)


def programs() -> dict:
    """``{name: Phases}`` of every program compiled since :func:`install`."""
    with _lock:
        return {n: replace(p) for n, p in _programs.items()}


def program(name: str) -> Phases | None:
    """The set-up of one program, by the name of its traced function."""
    with _lock:
        p = _programs.get(name)
        return None if p is None else replace(p)


def note_sweep(lanes: dict) -> None:
    """Keep the scan counters of a sweep's per-policy lane results."""
    global _last_sweep
    _last_sweep = {
        p: (r.active_steps, r.scan_steps) for p, r in lanes.items()
    }


def last_sweep() -> dict:
    """``{policy: (active_steps, scan_steps)}`` of the last ``run_sweep``."""
    return dict(_last_sweep)
