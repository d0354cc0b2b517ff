"""Kernel dispatch layer: model-facing ops with backend selection.

Every op takes the *model* layout and an ``impl`` argument:

  'auto'    pallas on TPU, XLA reference elsewhere (CPU dry-run/compile,
            GPU portability) — the default
  'pallas'  force the Pallas kernel (tests pass interpret=True on CPU)
  'xla'     the blocked/chunked pure-jnp implementation (flash-style)
  'naive'   the materialised oracle (tests/small shapes only)

The dry-run lowers through the 'xla' path: Pallas kernels cannot be
SPMD-partitioned across the production mesh without custom_partitioning,
and the roofline is derived from the XLA HLO.  On a real TPU pod the
per-shard call sites (shard_map granularity) switch to 'pallas'.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_pallas
from .doneprefix import (
    done_prefix_batch_pallas,
    done_prefix_packed_pallas,
    done_prefix_pallas,
)
from .flash_attention import flash_attention_pallas
from .rmsnorm import rmsnorm_pallas
from .rwkv6 import rwkv6_pallas
from .ssd import ssd_pallas

__all__ = [
    "attention",
    "decode_attention",
    "rmsnorm",
    "rwkv6",
    "rwkv6_step",
    "ssd",
    "ssd_step",
    "done_prefix",
    "done_prefix_batch",
    "done_prefix_packed",
    "pack_bits_u32",
    "first_set_bits",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "xla"
    return impl


# ----------------------------------------------------------------------
# attention: [B, S, H, D] model layout
# ----------------------------------------------------------------------
def attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, Hkv, D]
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    impl: str = "auto",
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "naive":
        return ref.attention_ref(q, k, v, causal=causal, scale=scale, q_offset=q_offset)
    if impl == "xla":
        return ref.flash_attention_ref(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset, block_k=block_k
        )
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    qk = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kk = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk := k.shape[1], D)
    vk = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    o = flash_attention_pallas(
        qk, kk, vk, causal=causal, scale=scale, q_offset=q_offset, interpret=interpret
    )
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def decode_attention(
    q: jax.Array,  # [B, H, D] — one new token per sequence
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] int32
    scale: Optional[float] = None,
    impl: str = "auto",
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("naive", "xla"):
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale)
    B, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qk = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kk = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vk = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    o = decode_attention_pallas(
        qk, kk, vk, lengths, scale=scale, block_k=block_k, interpret=interpret
    )
    return o.reshape(B, Hkv, G, D).reshape(B, H, D)


# ----------------------------------------------------------------------
# rmsnorm: [..., D]
# ----------------------------------------------------------------------
def rmsnorm(
    x: jax.Array,
    weight: jax.Array,
    eps: float = 1e-5,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("naive", "xla"):
        return ref.rmsnorm_ref(x, weight, eps=eps)
    return rmsnorm_pallas(x, weight, eps=eps, interpret=interpret)


# ----------------------------------------------------------------------
# rwkv6: model layout r/k/v/w [B, T, H, N], u [H, N], state [B, H, N, N]
# ----------------------------------------------------------------------
def rwkv6(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: Optional[jax.Array] = None,
    chunk: int = 32,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    B, T, H, N = r.shape
    if state is None:
        state = jnp.zeros((B, H, N, N), jnp.float32)
    pad = (-T) % chunk
    if pad and impl != "naive":
        def zpad(a):
            return jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))

        # pad with w=1 (no decay) and k=0 (no contribution)
        r2, k2, v2 = zpad(r), zpad(k), zpad(v)
        w2 = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    else:
        r2, k2, v2, w2 = r, k, v, w
    Tp = T + (pad if impl != "naive" else 0)

    if impl == "naive":
        fn = jax.vmap(
            jax.vmap(ref.rwkv6_scan_ref, in_axes=(1, 1, 1, 1, 0, 0), out_axes=(1, 0)),
            in_axes=(0, 0, 0, 0, None, 0),
            out_axes=(0, 0),
        )
        o, s = fn(r, k, v, w, u, state)
        return o, s
    if impl == "xla":
        fn = jax.vmap(
            jax.vmap(
                functools.partial(ref.rwkv6_chunk_ref, chunk=chunk),
                in_axes=(1, 1, 1, 1, 0, 0),
                out_axes=(1, 0),
            ),
            in_axes=(0, 0, 0, 0, None, 0),
            out_axes=(0, 0),
        )
        o, s = fn(r2, k2, v2, w2, u, state)
        return o[:, :T], s
    # pallas: fold (B, H) -> BH rows
    def fold(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, Tp, N)

    uu = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    o, s = rwkv6_pallas(
        fold(r2),
        fold(k2),
        fold(v2),
        fold(w2),
        uu,
        state.reshape(B * H, N, N),
        chunk=chunk,
        interpret=interpret,
    )
    o = o.reshape(B, H, Tp, N).transpose(0, 2, 1, 3)[:, :T]
    return o, s.reshape(B, H, N, N)


def rwkv6_step(
    r: jax.Array,  # [B, H, N] one token
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # [H, N]
    state: jax.Array,  # [B, H, N, N]
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step — O(N^2) per head, pure jnp (memory-bound)."""
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    Sf = state.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]  # [B,H,N,N]
    o = jnp.einsum("bhij,bhi->bhj", Sf + u[None, :, :, None] * kv, rf)
    S_new = wf[..., :, None] * Sf + kv
    return o.astype(r.dtype), S_new


# ----------------------------------------------------------------------
# ssd: model layout x [B, T, H, P], dt [B, T, H], A [H], B/C [B, T, G, N],
#      D [H], state [B, H, P, N]
# ----------------------------------------------------------------------
def ssd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    state: Optional[jax.Array] = None,
    chunk: int = 64,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    Bb, T, H, P = x.shape
    G = B.shape[2]
    N = B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)  # [B, T, H, N]
    Ch = jnp.repeat(C, rep, axis=2)
    if state is None:
        state = jnp.zeros((Bb, H, P, N), jnp.float32)
    pad = (-T) % chunk
    if pad and impl != "naive":
        def zp(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

        x2, dt2, Bh2, Ch2 = zp(x), zp(dt), zp(Bh), zp(Ch)
    else:
        x2, dt2, Bh2, Ch2 = x, dt, Bh, Ch
    Tp = x2.shape[1]

    if impl in ("naive", "xla"):
        core = (
            ref.ssd_scan_ref
            if impl == "naive"
            else functools.partial(ref.ssd_chunk_ref, chunk=chunk)
        )
        fn = jax.vmap(  # over H
            jax.vmap(core, in_axes=(0, 0, None, 0, 0, None, 0), out_axes=(0, 0)),
            in_axes=(2, 2, 0, 2, 2, 0, 1),
            out_axes=(2, 1),
        )
        y, s = fn(x2, dt2, A, Bh2, Ch2, D, state)
        return y[:, :T], s
    # pallas
    def fold3(a):
        return a.transpose(0, 2, 1, 3).reshape(Bb * H, Tp, a.shape[-1])

    xk = fold3(x2)
    dtk = dt2.transpose(0, 2, 1).reshape(Bb * H, Tp)
    Ak = jnp.broadcast_to(A[None], (Bb, H)).reshape(Bb * H)
    y, s = ssd_pallas(
        xk,
        dtk,
        Ak,
        fold3(Bh2),
        fold3(Ch2),
        state.reshape(Bb * H, P, N),
        chunk=chunk,
        interpret=interpret,
    )
    y = y.reshape(Bb, H, Tp, P).transpose(0, 2, 1, 3)[:, :T]
    y = y + D[None, None, :, None] * x
    return y, s.reshape(Bb, H, P, N)


def ssd_step(
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B, H]
    A: jax.Array,  # [H]
    B: jax.Array,  # [B, G, N]
    C: jax.Array,  # [B, G, N]
    D: jax.Array,  # [H]
    state: jax.Array,  # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the SSD recurrence (pure jnp)."""
    G = B.shape[1]
    H = x.shape[1]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)  # [B, H, N]
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(A[None].astype(jnp.float32) * dtf)  # [B, H]
    S_new = dA[..., None, None] * state + jnp.einsum(
        "bhp,bhn->bhpn", dtf[..., None] * xf, Bh
    )
    y = jnp.einsum("bhpn,bhn->bhp", S_new, Ch) + D[None, :, None] * xf
    return y.astype(x.dtype), S_new


# ----------------------------------------------------------------------
# COREC done-prefix
# ----------------------------------------------------------------------
def done_prefix(
    done: jax.Array,
    start: jax.Array,
    limit: jax.Array,
    impl: str = "auto",
    block_n: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("naive", "xla"):
        return ref.done_prefix_ref(done, start, limit)
    return done_prefix_pallas(done, start, limit, block_n=block_n, interpret=interpret)


def done_prefix_batch(
    done: jax.Array,  # [R, n] — one READ_DONE row per slot ring
    start: jax.Array,  # [R]
    limit: jax.Array,  # [R]
    impl: str = "auto",
    block_n: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Releasable prefixes of R slot rings in one kernel launch."""
    impl = _resolve(impl)
    if impl in ("naive", "xla"):
        return ref.done_prefix_batch_ref(done, start, limit)
    return done_prefix_batch_pallas(
        done, start, limit, block_n=block_n, interpret=interpret
    )


def pack_bits_u32(bits: jax.Array) -> jax.Array:
    """Pack a trailing bool (or 0/1 integer) axis into uint32 words
    (AtomicBitmap layout).

    ``bits[..., 32*j + b]`` becomes bit ``b`` of ``words[..., j]`` —
    the exact layout :func:`done_prefix_packed` consumes and
    ``core/ring.py``'s AtomicBitmap keeps on the threaded plane.  The
    lane engines pack their reconstructed claimed-masks through here in
    one shot instead of OR-ing per-claim deltas inside the scan.
    """
    *lead, n = bits.shape
    n_words = -(-n // 32)
    pad = [(0, 0)] * len(lead) + [(0, n_words * 32 - n)]
    b = jnp.pad(bits.astype(jnp.uint32), pad)
    b = b.reshape(*lead, n_words, 32)
    shifts = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(b * shifts, axis=-1, dtype=jnp.uint32)


def first_set_bits(words: jax.Array, k: int) -> jax.Array:
    """Positions of the ``k`` lowest set bits of one packed row.

    ``words`` is a single ``[n_words]`` uint32 bitmap in the
    AtomicBitmap layout of :func:`pack_bits_u32`; returns ``[k]`` int32
    positions in ascending order, padded with ``-1`` when fewer than
    ``k`` bits are set.  The TCP lane engine's SACK hole-scan uses this
    to pull the lowest retransmission holes out of a packed per-flow
    scoreboard without unpacking it; ``k`` is static, so the peel loop
    unrolls into ``k`` constant-shape find-lowest/clear rounds (vmap
    over rows/lanes from the caller).
    """
    w = words
    out = []
    for _ in range(k):
        nz = w != 0
        widx = jnp.argmax(nz).astype(jnp.int32)
        word = w[widx]
        low = word & (jnp.uint32(0) - word)  # lowest set bit
        pos = widx * 32 + jax.lax.population_count(low - 1).astype(jnp.int32)
        out.append(jnp.where(jnp.any(nz), pos, jnp.int32(-1)))
        w = w.at[widx].set(word ^ low)
    return jnp.stack(out)


def done_prefix_packed(
    words: jax.Array,  # [R, n_words] uint32 — packed bitmaps (bit b of
    limit: jax.Array,  # word j = slot 32*j + b), one row per lane/ring
    n_bits: Optional[int] = None,
    impl: str = "auto",
    block_w: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Contiguous done prefix of R word-packed bitmaps in one launch.

    The packed counterpart of :func:`done_prefix_batch`: consumes the
    AtomicBitmap word layout directly (as kept by the vectorized jax
    plane's claim accounting) instead of a bool-per-slot mask."""
    impl = _resolve(impl)
    if impl in ("naive", "xla"):
        return ref.done_prefix_packed_ref(words, limit, n_bits=n_bits)
    return done_prefix_packed_pallas(
        words, limit, n_bits=n_bits, block_w=block_w, interpret=interpret
    )
