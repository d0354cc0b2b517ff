"""Flash attention (prefill) Pallas TPU kernel — GQA, causal, online softmax.

TPU adaptation notes (vs. the CUDA FlashAttention algorithm):

* Tiling is chosen for the MXU (128x128 systolic array) and VMEM: the
  (block_q x d) Q tile, (block_k x d) K/V tiles and the (block_q x block_k)
  score tile are all multiples of 128 on their matmul dims for d_head in
  {64, 128}.
* The KV axis is the innermost *sequential* grid dimension; the running
  max / denominator / accumulator live in VMEM scratch across those grid
  steps (the Pallas-TPU idiom — CUDA keeps them in registers per CTA).
* GQA is handled in the index maps: query-head block h reads KV head
  h // group_size, so no materialised repeat_kv and no extra HBM traffic.

Layouts: q [BH, Sq, D], k/v [BKV, Sk, D] with BH = B * n_heads and
BKV = B * n_kv_heads (ops.py reshapes the model layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas"]

_NEG_INF = float("-inf")


def _flash_kernel(
    q_ref,  # [bq, D]
    k_ref,  # [bk, D]
    v_ref,  # [bk, D]
    o_ref,  # [bq, D]
    m_scr,  # [bq, 1] f32
    l_scr,  # [bq, 1] f32
    acc_scr,  # [bq, D] f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    sq: int,
    sk: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk]

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = (kpos < sk) & (qpos < sq)
    if causal:
        valid = valid & (qpos + q_offset >= kpos)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[...].astype(jnp.float32)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "scale",
        "q_offset",
        "block_q",
        "block_k",
        "group_size",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # [BH, Sq, D]
    k: jax.Array,  # [BKV, Sk, D]
    v: jax.Array,  # [BKV, Sk, D]
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    group_size: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = group_size if group_size is not None else BH // BKV
    assert BH == BKV * G, (BH, BKV, G)
    scale_v = scale if scale is not None else D ** -0.5

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    Sqp, Skp = nq * bq, nk * bk
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0)))

    kernel = functools.partial(
        _flash_kernel,
        scale=scale_v,
        block_q=bq,
        block_k=bk,
        causal=causal,
        sq=Sq,
        sk=Sk,
        q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq, :]
