"""Serving engine: correctness, work conservation, slot-ring semantics."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from repro.config import ArchConfig
from repro.serving import EngineConfig, InferenceEngine, Request

TINY = ArchConfig(
    "t",
    "dense",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab=256,
    attention_impl="xla",
    dtype="float32",
)


def _requests(n, new_tokens=4, prompt_len=6, sessions=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=list(map(int, rng.integers(2, 200, prompt_len))),
            max_new_tokens=new_tokens,
            session=int(rng.integers(0, sessions)),
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("policy", ["corec", "rss"])
def test_engine_completes_all_requests(policy):
    eng = InferenceEngine(
        TINY,
        EngineConfig(n_slots=4, max_seq=24, n_workers=2, policy=policy, eos_token=-1),
    )
    reqs = _requests(10)
    res = eng.run(reqs, timeout=90)
    assert len(res) == 10
    assert sorted(r.rid for r in res) == list(range(10))
    assert all(len(r.tokens) == 5 for r in res)  # first + 4 decoded
    assert all(r.t_done >= r.t_first_token >= r.t_arrival for r in res)


def test_unfinished_run_raises():
    """A run cut by its deadline raises instead of returning a partial
    result list; the finished requests stay on the engine."""
    eng = InferenceEngine(
        TINY,
        EngineConfig(n_slots=2, max_seq=24, n_workers=1, policy="corec", eos_token=-1),
    )
    with pytest.raises(TimeoutError, match="/6 requests finished"):
        eng.run(_requests(6, new_tokens=8), timeout=0.0)
    assert len(eng.results) < 6


def test_greedy_decode_deterministic_across_policies():
    """Same request => identical tokens regardless of ingestion policy
    (the queue discipline must not change model outputs)."""
    outs = {}
    for policy in ("corec", "rss"):
        eng = InferenceEngine(
            TINY,
            EngineConfig(
                n_slots=2, max_seq=24, n_workers=1, policy=policy, eos_token=-1
            ),
            rng=jax.random.PRNGKey(7),
        )
        res = eng.run(_requests(4, seed=5), timeout=90)
        outs[policy] = {r.rid: r.tokens for r in res}
    assert outs["corec"] == outs["rss"]


def test_contiguous_release_order():
    """Slot ring tail only advances over contiguous finished admissions."""
    eng = InferenceEngine(
        TINY,
        EngineConfig(
            n_slots=4,
            max_seq=24,
            n_workers=1,
            policy="corec",
            eos_token=-1,
            contiguous_release=True,
        ),
    )
    res = eng.run(_requests(8), timeout=90)
    assert len(res) == 8
    assert eng.tail == eng.head  # everything released at drain
    assert sum(eng.release_events) == eng.tail


def test_work_conservation_under_skewed_sessions():
    """All requests in ONE session: RSS pins them to one worker's queue;
    COREC lets both workers prefill.  COREC must not be slower.

    Wall-clock of two threaded engines on a shared CI box is noisy, so
    each policy's time is the best of three runs — the minimum is the
    least-interfered estimate of the engine's own cost, which is what
    the work-conservation claim is about.
    """
    t = {}
    for policy in ("corec", "rss"):
        best = float("inf")
        for _ in range(3):
            eng = InferenceEngine(
                TINY,
                EngineConfig(
                    n_slots=4, max_seq=24, n_workers=2, policy=policy, eos_token=-1
                ),
            )
            reqs = _requests(8, sessions=1, seed=9)
            t0 = time.perf_counter()
            res = eng.run(reqs, timeout=90)
            best = min(best, time.perf_counter() - t0)
            assert len(res) == 8
            if policy == "rss":
                workers = {r.worker for r in res}
                assert len(workers) == 1  # RSS pinned everything to one worker
        t[policy] = best
    assert t["corec"] <= t["rss"] * 1.5  # GIL-bound box: just no regression


def test_multilane_slot_rings_release_batched():
    """n_lanes > 1: all lanes' releasable prefixes come from ONE batched
    done-prefix kernel call; per-lane tails only advance over each lane's
    contiguous done prefix, and everything drains."""
    eng = InferenceEngine(
        TINY,
        EngineConfig(
            n_slots=8,
            max_seq=24,
            n_workers=2,
            policy="corec",
            eos_token=-1,
            contiguous_release=True,
            n_lanes=2,
        ),
    )
    res = eng.run(_requests(12), timeout=120)
    assert len(res) == 12
    assert sorted(r.rid for r in res) == list(range(12))
    assert eng.tail == eng.head  # every lane fully released at drain
    assert (eng.lane_tail == eng.lane_head).all()
    assert sum(eng.release_events) == eng.tail


def test_multilane_matches_single_lane_tokens():
    """Lane count is a scheduling detail: greedy outputs are identical."""
    outs = {}
    for lanes in (1, 2):
        eng = InferenceEngine(
            TINY,
            EngineConfig(
                n_slots=4,
                max_seq=24,
                n_workers=1,
                policy="corec",
                eos_token=-1,
                n_lanes=lanes,
            ),
            rng=jax.random.PRNGKey(3),
        )
        res = eng.run(_requests(6, seed=11), timeout=120)
        outs[lanes] = {r.rid: r.tokens for r in res}
    assert outs[1] == outs[2]
