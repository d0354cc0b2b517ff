"""COREC done-prefix scan — the paper's TAIL-advance, on device.

``read_batch_done`` (Listing 2 line 37) computes how many *contiguous*
completed slots start at TAIL; only that prefix may be returned to the
producer.  The serving engine keeps a device-resident READ_DONE mask for
its decode slot ring(s) (one bool per slot) and asks this kernel for the
releasable prefix each step, so slot recycling is computed on-TPU without
a host round-trip (host sync is the TPU analogue of the store-buffer
interference the paper's RMW instructions bypass).

Three entry points:

* ``done_prefix_pallas`` — one ``[n]`` mask, the one-ring case of the
  batch kernel below.  The mask axis is tiled over a multi-block grid
  (``block_n`` slots per block) so masks far larger than one VMEM tile
  still lower; blocks accumulate a running min into the row's result
  (sequential column axis), and the final block clamps by ``limit``.
* ``done_prefix_batch_pallas`` — ``[R, n]`` masks with per-ring ``start``
  /``limit`` vectors: the releasable prefix of *all* R decode slot rings
  in ONE ``pallas_call``, which is how the serving engine releases every
  lane per step with a single kernel launch instead of R.
* ``done_prefix_packed_pallas`` — ``[R, n_words]`` *word-packed* uint32
  bitmaps (bit b of word j = slot ``32*j + b``, the AtomicBitmap layout
  of ``core/ring.py`` and the claim bitmaps of the vectorized jax plane,
  :mod:`repro.core.jaxplane`).  The prefix is computed without ever
  unpacking to a bool mask: per word, the trailing-ones count is
  ``popcount((~w & -~w) - 1)`` (32 for an all-ones word), and the global
  prefix is the same masked-min reduction as above, over words instead
  of bits.  Sequence space is linear (no TAIL rotation) — the jax
  plane's claim bitmaps never wrap; ring-style rotation stays with the
  bool-mask kernels.

The rotation by ``start`` is done with an index comparison instead of a
gather (TPU-friendly), and the contiguous run length is a masked min:
``off`` is each slot's distance from ``start`` in ring order, and the
smallest not-done ``off`` *is* the run length.

TPU layout: the grid is ``(row blocks, column blocks)``.  A row block
holds ``_ROW_BLOCK`` rows (fewer, rounded up to the 8-row sublane tile,
when there are fewer rows); a column block is the whole row or a
multiple of the 128-lane tile.  Rows and columns are zero-padded to
whole blocks in the wrapper, and padded columns are masked by index, so
no block is ragged.  The per-row ``start`` / ``limit`` operands and the
result are ``[rows, 1]`` int32 columns: the result block stays resident
across the column axis and carries the running min as a vector, never
as a VMEM scalar.  Bool masks are widened to int32 before the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "done_prefix_pallas",
    "done_prefix_batch_pallas",
    "done_prefix_packed_pallas",
]

_DEFAULT_BLOCK = 512  # columns (slots or words) per block
_ROW_BLOCK = 256  # rows per block, a multiple of the 8-row sublane tile
_LANE = 128


def _col_block(n: int, block: int | None) -> int:
    """Columns per block: the whole row, or ``block`` rounded up to a
    multiple of the 128-lane tile when that still splits the row."""
    b = -(-(block or _DEFAULT_BLOCK) // _LANE) * _LANE
    return n if b >= n else b


def _tiles(rows: int, cols: int, block: int | None):
    tr = min(_ROW_BLOCK, -(-rows // 8) * 8)
    tc = _col_block(cols, block)
    return tr, -(-rows // tr) * tr, tc, -(-cols // tc) * tc


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def _col(v: jax.Array, rows: int) -> jax.Array:
    """A per-row ``[R]`` vector as a zero-padded ``[rows, 1]`` int32 column."""
    return _pad2(v.astype(jnp.int32)[:, None], rows, 1)


def _row_min_call(kernel, operands, tr: int, tc: int, interpret: bool):
    """Run ``kernel`` over ``[rows, 1]`` per-row columns + one
    ``[rows, cols]`` matrix (the last operand), one int32 per row out."""
    *cols, mat = operands
    rows, width = mat.shape
    col_spec = pl.BlockSpec((tr, 1), lambda r, i: (r, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // tr, width // tc),
        in_specs=[col_spec] * len(cols)
        + [pl.BlockSpec((tr, tc), lambda r, i: (r, i))],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*cols, mat)


def _accumulate(out_ref, local, limit, init: int):
    """Running min over the column blocks of one row block; the last
    block clamps by ``limit``."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, init, jnp.int32)

    cur = jnp.minimum(out_ref[...], local)
    is_last = i == pl.num_programs(1) - 1
    out_ref[...] = jnp.where(is_last, jnp.minimum(cur, limit), cur)


def _done_prefix_kernel(start_ref, limit_ref, done_ref, out_ref, *, n: int, bn: int):
    d = done_ref[...]  # [tr, bn] int32 tile of tr rings
    start = start_ref[...]  # [tr, 1]
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1) + pl.program_id(1) * bn
    # offset of each slot from start, in ring order
    off = jnp.where(idx >= start, idx - start, idx + n - start)
    # first not-done offset == run length; padded slots (idx >= n) and
    # done slots impose no constraint
    local = jnp.min(jnp.where((d == 0) & (idx < n), off, n), axis=1, keepdims=True)
    _accumulate(out_ref, local, limit_ref[...], n)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def done_prefix_batch_pallas(
    done: jax.Array,  # [R, n] bool — READ_DONE, one row per slot ring
    start: jax.Array,  # [R] int32 — TAIL slot index per ring
    limit: jax.Array,  # [R] int32 — cap per ring (claim_head - tail)
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:  # [R] int32
    R, n = done.shape
    tr, r_pad, bn, n_pad = _tiles(R, n, block_n)
    mask = _pad2(done.astype(jnp.int32), r_pad, n_pad)
    out = _row_min_call(
        functools.partial(_done_prefix_kernel, n=n, bn=bn),
        (_col(start, r_pad), _col(limit, r_pad), mask),
        tr,
        bn,
        interpret,
    )
    return out[:R, 0]


def _done_prefix_packed_kernel(
    limit_ref, words_ref, out_ref, *, n_bits: int, nw: int, bw: int
):
    w = words_ref[...]  # [tr, bw] uint32 tile of tr bitmaps
    idx = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) + pl.program_id(1) * bw
    # Trailing-ones count per word without unpacking: the first zero bit
    # of w is the lowest set bit of ~w; popcount of (lowbit - 1) counts
    # the ones below it.  All-ones words give ~w == 0 -> popcount of
    # 0xFFFFFFFF == 32 (no constraint from this word).
    x = ~w
    low = x & (jnp.uint32(0) - x)
    to = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    cand = idx * 32 + to
    local = jnp.min(
        jnp.where((to < 32) & (idx < nw), cand, n_bits), axis=1, keepdims=True
    )
    _accumulate(out_ref, local, limit_ref[...], n_bits)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "block_w", "interpret")
)
def done_prefix_packed_pallas(
    words: jax.Array,  # [R, n_words] uint32 — packed done/claim bitmaps
    limit: jax.Array,  # [R] int32 — cap per bitmap
    n_bits: int | None = None,  # logical bit count (default 32 * n_words)
    block_w: int | None = None,
    interpret: bool = False,
) -> jax.Array:  # [R] int32
    R, nw = words.shape
    if n_bits is None:
        n_bits = 32 * nw
    tr, r_pad, bw, nw_pad = _tiles(R, nw, block_w)
    out = _row_min_call(
        functools.partial(_done_prefix_packed_kernel, n_bits=n_bits, nw=nw, bw=bw),
        (_col(limit, r_pad), _pad2(words, r_pad, nw_pad)),
        tr,
        bw,
        interpret,
    )
    return out[:R, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def done_prefix_pallas(
    done: jax.Array,  # [n] bool — READ_DONE
    start: jax.Array,  # scalar int32 — TAIL slot index
    limit: jax.Array,  # scalar int32 — at most this many (claim_head - tail)
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    out = done_prefix_batch_pallas(
        done[None, :],
        jnp.atleast_1d(start),
        jnp.atleast_1d(limit),
        block_n=block_n,
        interpret=interpret,
    )
    return out[0]
