"""The claim step counts arrivals and service budgets without a search loop.

* :func:`jaxplane.row_arrived` / :func:`jaxplane.rows_arrived` give the
  integers of ``jnp.searchsorted(..., side="right", method="scan")`` and
  of ``numpy.searchsorted`` on sorted rows: +inf padding, ties, and
  queries at -inf, +inf, NaN, -0.0 (rows hold +0.0), on a row value and
  between values;
* one vmapped ``_claim_step`` of every built-in policy traces to a jaxpr
  with no ``while`` or ``scan``, nested jaxprs included, so no search
  loop runs inside the scan step.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.jaxplane import (  # noqa: E402
    JAX_POLICIES,
    LaneParams,
    OverloadConfig,
    ServingParams,
    _broadcast_lanes,
    _claim_step,
    _init_state,
    default_lane_params,
    default_serving_params,
    row_arrived,
    rows_arrived,
)

ROWS = 6
QUERIES = ["-inf", "+inf", "nan", "-0.0", "on_value", "between"]


def _padded_rows(length: int, seed: int) -> np.ndarray:
    """``[ROWS, length]`` sorted float32 rows: a random count of finite
    entries drawn from few values (so ties occur), then +inf padding.
    The first row is all padding and the last has none."""
    rng = np.random.default_rng(seed)
    rows = np.full((ROWS, length), np.inf, np.float32)
    for r in range(ROWS):
        n_fin = length if r == ROWS - 1 else rng.integers(0, length + 1) if r else 0
        vals = rng.integers(0, max(2, length // 3), size=n_fin) * np.float32(0.5)
        rows[r, :n_fin] = np.sort(vals.astype(np.float32))
    return rows


def _query(row: np.ndarray, kind: str, rng) -> np.float32:
    if kind in ("-inf", "+inf", "nan", "-0.0"):
        return np.float32(kind)
    fin = row[np.isfinite(row)]
    if kind == "on_value":
        # an entry of the row (a tie when it repeats); +inf on a padded row
        return np.float32(rng.choice(fin)) if fin.size else np.float32(np.inf)
    # strictly between two entries, or beside the only value there is
    vals = np.unique(fin)
    if vals.size >= 2:
        i = rng.integers(0, vals.size - 1)
        return np.float32((vals[i] + vals[i + 1]) / 2)
    return np.float32(vals[0] + 0.25 if vals.size else 1.0)


@pytest.mark.parametrize("kind", QUERIES)
@pytest.mark.parametrize("length", [1, 2, 64, 2001])
def test_count_matches_scan_search_and_numpy(length, kind):
    rows = _padded_rows(length, seed=length)
    rng = np.random.default_rng(length + 7)
    ts = np.array([_query(r, kind, rng) for r in rows], np.float32)

    want = np.array(
        [np.searchsorted(r, t, side="right") for r, t in zip(rows, ts)], np.int32
    )
    scan = jax.jit(
        jax.vmap(lambda r, t: jnp.searchsorted(r, t, side="right", method="scan"))
    )(rows, ts)
    got = jax.jit(jax.vmap(row_arrived))(rows, ts)
    np.testing.assert_array_equal(np.asarray(scan), want)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert got.dtype == jnp.int32

    # [W, n+1] queue rows against one claim instant, as the step asks
    for t in ts:
        want_w = np.array([np.searchsorted(r, t, side="right") for r in rows])
        got_w = jax.jit(rows_arrived)(rows, t)
        np.testing.assert_array_equal(np.asarray(got_w), want_w)
        assert got_w.dtype == jnp.int32


def _loop_primitives(jaxpr) -> set:
    """Names of ``while`` / ``scan`` equations in ``jaxpr`` and every
    sub-jaxpr (``pjit``, ``cond`` branches, custom rules)."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            found.add(eqn.primitive.name)
        todo = list(eqn.params.values())
        while todo:
            p = todo.pop()
            if isinstance(p, (tuple, list)):
                todo.extend(p)
            elif hasattr(p, "eqns"):
                found |= _loop_primitives(p)
            elif hasattr(p, "jaxpr") and hasattr(p.jaxpr, "eqns"):
                found |= _loop_primitives(p.jaxpr)
    return found


MODES = {
    "fault-free": (False, OverloadConfig()),
    "serving": (True, OverloadConfig()),
    "overload": (True, OverloadConfig(breaker_age=5.0, scale_latency=3.0)),
}


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("policy", sorted(JAX_POLICIES))
def test_claim_step_has_no_loop(policy, mode):
    serving, ov = MODES[mode]
    lanes, w, n, mb = 3, 4, 16, 8
    params = LaneParams(
        *_broadcast_lanes(default_lane_params(), LaneParams._fields, lanes)
    )
    sparams = ServingParams(
        *_broadcast_lanes(default_serving_params(), ServingParams._fields, lanes)
    )
    q_arr = jnp.zeros((lanes, w, n + 1), jnp.float32)
    cumsvc = jnp.zeros((lanes, w, n), jnp.float32)
    flt = (
        jnp.full((lanes, w), jnp.inf, jnp.float32),
        jnp.ones((lanes, w), jnp.float32),
        jnp.full((lanes,), jnp.inf, jnp.float32),
    )
    st = _init_state(lanes, w)
    u = stall = jnp.zeros((lanes,), jnp.float32)
    step = jax.vmap(lambda *a: _claim_step(JAX_POLICIES[policy], mb, serving, ov, *a))
    closed = jax.make_jaxpr(step)(params, sparams, q_arr, cumsvc, flt, st, u, stall)
    assert closed.jaxpr.eqns
    assert _loop_primitives(closed.jaxpr) == set()


def test_loop_walk_finds_a_nested_search():
    """The walk reaches a ``scan`` inside a ``pjit``, so an empty result
    above means no loop, not a missed sub-jaxpr."""
    fn = jax.jit(lambda r, t: jnp.searchsorted(r, t, method="scan"))
    closed = jax.make_jaxpr(lambda r, t: fn(r, t) + 1)(jnp.zeros(8), 0.5)
    assert _loop_primitives(closed.jaxpr) == {"scan"}
