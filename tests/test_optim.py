"""Optimizer, schedules, and gradient-compression tests."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import (
    AdamW,
    apply_updates,
    compressed_pod_allreduce,
    cosine_schedule,
    dequantize_int8,
    error_feedback_init,
    global_norm,
    quantize_int8,
    wsd_schedule,
)


def test_adamw_decreases_quadratic():
    params = {"w": jnp.array([3.0, -2.0, 1.0])}
    opt = AdamW(weight_decay=0.0)
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params, jnp.float32(0.05))
        params = apply_updates(params, upd)
    assert float(loss(params)) < 1e-3


def test_grad_clipping():
    opt = AdamW(clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    huge = {"w": jnp.full(3, 1e6)}
    upd, state = opt.update(huge, state, params, jnp.float32(1.0))
    # post-clip the step magnitude is bounded by lr * O(1)
    assert float(jnp.abs(upd["w"]).max()) < 2.0


def test_schedules_shapes():
    cos = cosine_schedule(1.0, warmup=10, total=100)
    assert float(cos(0)) == 0.0
    assert abs(float(cos(10)) - 1.0) < 1e-6
    assert float(cos(100)) < float(cos(50))
    wsd = wsd_schedule(1.0, warmup=10, stable=50, decay=20)
    assert abs(float(wsd(30)) - 1.0) < 1e-6  # stable phase
    assert float(wsd(75)) < 0.7  # decaying


def test_int8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)) * 3.0)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.51 + 1e-6


def test_compressed_allreduce_with_error_feedback():
    """Inside shard_map over a pod axis: mean-reduction error is bounded
    per step and error feedback keeps the *accumulated* bias near zero."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh

    mesh = make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(128,)))}
    e = error_feedback_init(g)

    def f(g, e):
        return compressed_pod_allreduce(g, e, "pod")

    fm = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )
    red, e2 = fm(g, e)
    # single pod: reduction == dequant(quant(g)); residual = g - that
    np.testing.assert_allclose(
        np.asarray(red["w"] + e2["w"]), np.asarray(g["w"]), rtol=1e-6, atol=1e-6
    )
    # 100 steps of the same gradient: error feedback keeps mean bias ~0
    acc = jnp.zeros_like(g["w"])
    e = error_feedback_init(g)
    for _ in range(100):
        red, e = fm(g, e)
        acc = acc + red["w"]
    np.testing.assert_allclose(np.asarray(acc / 100), np.asarray(g["w"]), atol=2e-3)


def test_global_norm():
    t = {"a": jnp.ones(4), "b": jnp.ones(9)}
    assert abs(float(global_norm(t)) - np.sqrt(13.0)) < 1e-6
