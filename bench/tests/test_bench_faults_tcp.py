"""A TCP run with the timed path broken reads ``correct`` false."""

import faults
import pytest

from repro.core import tcpjax


@pytest.fixture(scope="module")
def cell():
    return faults.small_cell("tcp-grid")


def test_sound_run_is_correct(monkeypatch, cell):
    out = faults.run_small(monkeypatch, cell)
    assert out["correct"], faults.failed(out)


def test_step_that_returns_its_state_unchanged(monkeypatch, cell):
    orig = tcpjax._tcp_step

    def stuck(*args, st, xs, **kw):
        out = orig(*args, st=dict(st), xs=xs, **kw)
        return (st,) + tuple(out[1:])

    monkeypatch.setattr(tcpjax, "_tcp_step", stuck)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert "undone_flows" in faults.failed(out)


def test_half_of_the_lanes_left_out(monkeypatch, cell):
    monkeypatch.setattr(
        tcpjax, "run_tcp_lanes_fused", faults.halve_lanes(tcpjax.run_tcp_lanes_fused)
    )
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]


def test_answer_altered_where_it_is_produced(monkeypatch, cell):
    orig = tcpjax._tcp_outputs

    def late(*args, **kw):
        out = orig(*args, **kw)
        return dict(out, fct=out["fct"] * 1.01)

    monkeypatch.setattr(tcpjax, "_tcp_outputs", late)
    out = faults.run_small(monkeypatch, cell)
    assert not out["correct"]
    assert "fct_gap_median" in faults.failed(out)
