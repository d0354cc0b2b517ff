"""Mamba2 SSD (state-space dual) chunk scan — Pallas TPU kernel.

Same chunking idea as the RWKV6 kernel but with *scalar* per-head decay
(Mamba2's A is a scalar per head), which makes the intra-chunk decay matrix
a rank-structured [C, C] segment-sum — cheap on the VPU — and the heavy
lifting two MXU matmuls per chunk: (C_t . B_s) gating and the state
update/readout against the carried [P, N] state.

Grid: (BH, T // chunk), state carried in VMEM scratch over the sequential
chunk dim.  Layouts: x [BH, T, P], dt [BH, T, 1], A [BH, 1, 1],
B/C [BH, T, N]; outputs y [BH, T, P], final state [BH, P, N].
The D-skip (y += D x) is applied by ops.py outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_pallas"]


def _ssd_kernel(
    x_ref,  # [C, P]
    dt_ref,  # [C, 1]
    a_ref,  # [1, 1]
    b_ref,  # [C, N]
    c_ref,  # [C, N]
    s0_ref,  # [P, N]
    y_ref,  # [C, P]
    sout_ref,  # [P, N]
    S_scr,  # [P, N] f32
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        S_scr[...] = s0_ref[...].astype(jnp.float32)

    xc = x_ref[...].astype(jnp.float32)
    dtc = dt_ref[...].astype(jnp.float32)  # [C, 1]
    A = a_ref[0, 0].astype(jnp.float32)
    Bc = b_ref[...].astype(jnp.float32)
    Cc = c_ref[...].astype(jnp.float32)

    ladt = A * dtc  # [C, 1] log decay per step
    lcum = jnp.cumsum(ladt, axis=0)  # inclusive
    L = lcum - lcum.reshape(1, -1)  # [t, s] log decay t<-s
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    G = jnp.where(ti >= si, jnp.exp(L), 0.0) * jax.lax.dot_general(
        Cc, Bc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = jax.lax.dot_general(
        G, dtc * xc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    S = S_scr[...]
    y = y + jnp.exp(lcum) * jax.lax.dot_general(
        Cc, S, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)

    lend = lcum[-1:, :]  # [1, 1]
    decay_to_end = jnp.exp(lend - lcum)  # [C, 1]
    S_new = jnp.exp(lend[0, 0]) * S + jax.lax.dot_general(
        decay_to_end * dtc * xc,
        Bc,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    S_scr[...] = S_new

    @pl.when(ci == pl.num_programs(1) - 1)
    def _finish():
        sout_ref[...] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,  # [BH, T, P]
    dt: jax.Array,  # [BH, T]
    A: jax.Array,  # [BH]
    B: jax.Array,  # [BH, T, N]
    C: jax.Array,  # [BH, T, N]
    state: jax.Array,  # [BH, P, N]
    chunk: int = 64,
    interpret: bool = False,
):
    BH, T, P = x.shape
    N = B.shape[-1]
    assert T % chunk == 0, "ops.py pads T to a chunk multiple"
    nc = T // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt.reshape(BH, T, 1), A.reshape(BH, 1, 1), B, C, state)
    return y, s_out
