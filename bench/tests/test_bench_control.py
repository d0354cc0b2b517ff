"""The control, the plain reference computed in bfloat16 in the
program's place, fails the comparison that sound runs pass."""

import faults
import jax
import pytest

import control


@pytest.mark.parametrize(
    "name,config",
    [("fwd-udp", {"packets_per_lane": 256}), ("fwd-bursty", {"packets_per_lane": 256}),
     ("tcp-grid", {})],
)
def test_control_fails_where_the_program_passes(name, config):
    cell = faults.small_cell(name, **config)
    jax.clear_caches()
    line = control.readings(cell, 2**31 + 7, control=True)
    limits = cell.limits["limits"]
    skip = set(cell.limits.get("not_compared", ()))

    def over(nums):
        return [k for k, v in nums.items() if k not in skip and v > limits[k]]

    assert over(line["sound"]) == []
    assert over(line["control"])
