"""The ``serve`` and ``lane_setup`` name scopes of the fused sweep.

``serve`` marks the serving branches of the claim step (the autoscale
wake gate and shed-at-claim admission) and ``lane_setup`` each policy
segment's lane set-up.  They are op metadata only: with them taken out,
every lane result and the compiled program, its metadata aside, are the
same.
"""

from __future__ import annotations

import contextlib
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import SweepRequest, jax_policies, jaxplane, run_sweep  # noqa: E402

SERVING = SweepRequest(
    scenario="serving",
    arrival="diurnal",
    seeds=np.arange(4),
    n_packets=96,
    chunk=32,
    lane_params=dict(deschedule_prob=np.array([0.0, 0.005, 0.0, 0.005])),
    traffic_params=dict(rate=np.array([2.0, 3.0, 4.0, 5.0])),
    serving_params=dict(admit_limit=16.0, base_workers=2.0, scale_backlog=12.0),
    use_policy_serving_defaults=False,
)
FORWARDER = SweepRequest(
    seeds=np.arange(4), n_packets=120, chunk=32, lane_params=dict(batch=4)
)
METADATA = re.compile(r", metadata=\{[^}]*\}")
#: the tables of source files, functions and stack frames the metadata indexes
FRAMES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)\n", re.M
)


#: instruction and computation names, which XLA may take from source locations
NAMES = re.compile(r"%[\w.-]+")


def _ops(hlo: str) -> str:
    """HLO text without op metadata and the tables it indexes, each name
    replaced by the order of its first use."""
    seen: dict = {}
    text = METADATA.sub("", FRAMES.sub("", hlo))
    return NAMES.sub(lambda m: seen.setdefault(m.group(), f"%v{len(seen)}"), text)


def _run(monkeypatch, req):
    """``req``'s results and its compiled fused program as HLO text."""
    texts = []
    orig = jaxplane._call_fused

    def grab(fn, args, static, timings):
        texts.append(fn.lower(*args, **static).compile().as_text())
        return orig(fn, args, static, timings)

    with monkeypatch.context() as m:
        m.setattr(jaxplane, "_call_fused", grab)
        res = run_sweep(req)
    assert len(texts) == 1
    return res, texts[0]


def test_serving_program_carries_both_scopes(monkeypatch):
    _, hlo = _run(monkeypatch, SERVING)
    for p in jax_policies():
        assert f"/seg.{p}/lane_setup/" in hlo, p
        # the step is vmapped inside the scan's chunk loop
        assert re.search(rf"/seg\.{re.escape(p)}/scan/.*/vmap\(serve\)/", hlo), p


def test_forwarder_program_has_lane_setup_and_no_serve(monkeypatch):
    _, hlo = _run(monkeypatch, FORWARDER)
    assert "/lane_setup/" in hlo
    assert "serve)" not in hlo and "/serve/" not in hlo


@pytest.mark.parametrize("req", [FORWARDER, SERVING], ids=["forwarder", "serving"])
def test_scopes_change_no_result_and_no_op(monkeypatch, req):
    with_scopes, hlo = _run(monkeypatch, req)
    real = jax.named_scope

    def scope(name):
        if name in ("serve", "lane_setup"):
            return contextlib.nullcontext()
        return real(name)

    jax.clear_caches()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", scope)
            without, bare = _run(monkeypatch, req)
    finally:
        jax.clear_caches()
    assert "/lane_setup/" not in bare and "/lane_setup/" in hlo
    assert _ops(hlo) == _ops(bare)
    for p in with_scopes.policies:
        a, b = with_scopes[p], without[p]
        for field in a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)), field
            )
