"""What the program recorded about itself in this process: set-up
phases of the cell's fused program and the scan counters of the last
sweep (``repro.core.record``).  A program without that record gives
nothing here."""

from __future__ import annotations

#: the fused program each scenario compiles, by its traced function
PROGRAM = {"forwarder": "_run_fused_impl", "tcp": "_run_tcp_fused_impl"}


def _record():
    try:
        from repro.core import record
    except ImportError:
        return None
    return record


def setup_seconds(ctx: dict, phase: str):
    """Seconds of one set-up phase (``trace_s``, ``lower_s``,
    ``load_s``) of the cell's fused program, summed over its compiles
    in this process; none where the program kept no record of it."""
    record = _record()
    name = PROGRAM.get(ctx["scenario"])
    phases = None if record is None or name is None else record.program(name)
    if phases is None or not phases.compiles:
        return None
    return getattr(phases, phase)


def lane_steps():
    """``(active, scanned)``: the last sweep's ``active_steps`` and
    ``scan_steps`` summed over every lane of every policy; none where
    the program keeps no such counters."""
    record = _record()
    counters = {} if record is None else record.last_sweep()
    if not counters:
        return None
    import numpy as np

    active = sum(int(np.asarray(a, np.int64).sum()) for a, _ in counters.values())
    scanned = sum(int(np.asarray(s, np.int64).sum()) for _, s in counters.values())
    return active, scanned
