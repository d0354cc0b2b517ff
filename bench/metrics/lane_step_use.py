"""Share of the scanned lane-steps in which the lane still had work:
the sum of ``active_steps`` over the sum of ``scan_steps``, over every
lane of the traced call (the program's counters, ``harness.phases``)."""

from harness import phases


def read(ctx):
    steps = phases.lane_steps()
    if steps is None or not steps[1]:
        return None
    active, scanned = steps
    return 100.0 * active / scanned
