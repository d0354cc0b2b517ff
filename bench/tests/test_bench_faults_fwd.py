"""A forwarder run with the timed path broken reads ``correct`` false."""

import faults
import numpy as np
import pytest

from repro.core import jaxplane
from repro.kernels import ops as kernel_ops


@pytest.fixture(scope="module")
def cell():
    return faults.small_cell("fwd-udp", packets_per_lane=256)


def test_sound_run_is_correct(monkeypatch, cell):
    out = faults.run_small(monkeypatch, cell)
    assert out["correct"], faults.failed(out)
    assert out["checks"]["window_compilations"]["value"] == 0


def test_step_that_returns_its_state_unchanged(monkeypatch, cell):
    orig = jaxplane._claim_step

    def stuck(*args):
        _, rec = orig(*args)
        return args[9], rec

    monkeypatch.setattr(jaxplane, "_claim_step", stuck)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert "exactly_once_bad_lanes" in faults.failed(out)


def test_half_of_the_lanes_left_out(monkeypatch, cell):
    half = faults.halve_lanes(jaxplane._fused_lanes)
    monkeypatch.setattr(jaxplane, "_fused_lanes", half)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert any(k.endswith("_gap") for k in faults.failed(out))


def test_answer_altered_where_it_is_produced(monkeypatch, cell):
    orig = jaxplane.reorder_metrics

    def nudged(done):
        ratio, dist = orig(done)
        return ratio + 0.005, dist

    monkeypatch.setattr(jaxplane, "reorder_metrics", nudged)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert "reorder_pct_gap_median" in faults.failed(out)


def test_kernel_prefix_altered(monkeypatch, cell):
    orig = kernel_ops.done_prefix_packed

    def short(words, limit, **kw):
        pre = orig(words, limit, **kw)
        return pre - (np.arange(pre.shape[0]) == 0).astype(pre.dtype)

    monkeypatch.setattr(kernel_ops, "done_prefix_packed", short)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert "prefix_mismatch_lanes" in faults.failed(out)
