"""Decode (single-token) attention Pallas TPU kernel — flash-decode style.

The decode hot loop is memory-bound: each new token must stream the whole
KV cache from HBM once.  The kernel therefore:

* streams K/V in ``block_k`` tiles (innermost sequential grid dim) and
  keeps the (G x block_k) score tile plus the online-softmax running
  stats in VMEM — one HBM pass, no materialised [S] score row in HBM;
* packs the GQA group dim G as the matmul M dimension, so the MXU sees a
  (G x D) @ (D x block_k) problem per tile instead of G rank-1 products;
* masks by per-sequence cache ``length`` (continuous batching: sequences
  in one batch have different lengths), passed as scalar-prefetch so the
  index map could *prune* fully-invalid tail blocks on real hardware.

Layouts: q [BKV, G, D] (one token per sequence), k/v [BKV, S, D],
lengths [B] int32 with BKV = B * n_kv_heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["decode_attention_pallas"]

_NEG_INF = float("-inf")


def _decode_kernel(
    lengths_ref,  # scalar-prefetch: [B] int32
    q_ref,  # [G, D]
    k_ref,  # [bk, D]
    v_ref,  # [bk, D]
    o_ref,  # [G, D]
    m_scr,  # [G, 1]
    l_scr,  # [G, 1]
    acc_scr,  # [G, D]
    *,
    scale: float,
    block_k: int,
    n_kv_heads: int,
):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    length = lengths_ref[bh // n_kv_heads]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale  # [G, D]
    k = k_ref[...].astype(jnp.float32)  # [bk, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [G, bk]
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < length
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p,
        v_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention_pallas(
    q: jax.Array,  # [BKV, G, D]
    k: jax.Array,  # [BKV, S, D]
    v: jax.Array,  # [BKV, S, D]
    lengths: jax.Array,  # [B] int32
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    BKV, G, D = q.shape
    S = k.shape[1]
    B = lengths.shape[0]
    n_kv_heads = BKV // B
    scale_v = scale if scale is not None else D ** -0.5

    bk = min(block_k, S)
    nk = -(-S // bk)
    Sp = nk * bk
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))

    kernel = functools.partial(
        _decode_kernel, scale=scale_v, block_k=bk, n_kv_heads=n_kv_heads
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV, nk),
        in_specs=[
            pl.BlockSpec((None, G, D), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((None, bk, D), lambda b, j, *_: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, j, *_: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, G, D), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BKV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, q, k, v)
    return out
