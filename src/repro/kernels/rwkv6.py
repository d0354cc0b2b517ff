"""RWKV6 (Finch) WKV recurrence — chunked-parallel Pallas TPU kernel.

The WKV recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T is sequential per
token; a token-by-token scan starves the MXU.  The TPU adaptation runs the
*chunked* form (see kernels/ref.py::rwkv6_chunk_ref): within a chunk of C
tokens everything is dense (C x N) matmuls; only the (N x N) state crosses
chunk boundaries, carried in VMEM scratch across the sequential innermost
grid dimension.  Decay products are computed in log space on the VPU.

Grid: (BH, T // chunk) with dimension_semantics ("parallel", "arbitrary").
Layouts (ops.py maps the model layout): r/k/v/w [BH, T, N], u [BH, N];
outputs o [BH, T, N] and the final state [BH, N, N] for serving.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["rwkv6_pallas"]


def _rwkv6_kernel(
    r_ref,  # [C, N]
    k_ref,
    v_ref,
    w_ref,
    u_ref,  # [1, N]
    s0_ref,  # [N, N] initial state
    o_ref,  # [C, N]
    sout_ref,  # [N, N]
    S_scr,  # [N, N] f32 carry
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        S_scr[...] = s0_ref[...].astype(jnp.float32)

    rc = r_ref[...].astype(jnp.float32)
    kc = k_ref[...].astype(jnp.float32)
    vc = v_ref[...].astype(jnp.float32)
    lw = jnp.log(jnp.maximum(w_ref[...].astype(jnp.float32), 1e-30))
    u = u_ref[...].astype(jnp.float32)  # [1, N]

    la = jnp.cumsum(lw, axis=0)  # log a_t inclusive
    la_prev = la - lw  # exclusive
    r_decay = rc * jnp.exp(la_prev)
    k_scaled = kc * jnp.exp(-la)

    A = jax.lax.dot_general(
        r_decay, k_scaled, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [t, s]
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(ti > si, A, 0.0)  # strictly lower triangular
    diag = jnp.sum(rc * (u * kc), axis=-1, keepdims=True)  # [C, 1]
    S = S_scr[...]
    o = (
        jax.lax.dot_general(
            A, vc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        + diag * vc
        + jax.lax.dot_general(
            r_decay, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    )
    o_ref[...] = o.astype(o_ref.dtype)

    la_end = la[-1:, :]  # [1, N]
    S_new = jnp.exp(la_end).T * S + jax.lax.dot_general(
        kc * jnp.exp(la_end - la),
        vc,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    S_scr[...] = S_new

    @pl.when(ci == pl.num_programs(1) - 1)
    def _finish():
        sout_ref[...] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_pallas(
    r: jax.Array,  # [BH, T, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # [BH, N]
    state: jax.Array,  # [BH, N, N]
    chunk: int = 32,
    interpret: bool = False,
):
    BH, T, N = r.shape
    assert T % chunk == 0, "ops.py pads T to a chunk multiple"
    nc = T // chunk
    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1, N), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((None, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, N), r.dtype),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, N), state)
    return o, s_out
