"""Vectorized TCP lane engine: the jax plane's closed-loop scenario.

:mod:`repro.core.tcp` evaluates one (policy, config, seed) TCP point
per Python event loop — the COREC worst case (one large TCP flow, the
paper bounds the reordering penalty at 2-3%) costs minutes of wall
clock to sweep.  This module restates that closed loop — senders -->
access link --> policy-driven forwarder --> receiver --> ACKs --> the
window — as a pure ``lax.scan`` step function over fixed-size per-flow
state arrays, evaluated for every (policy-param, seed) lane of every
requested policy in ONE jitted call (:func:`run_tcp_lanes_fused`).

The DES event heap becomes a four-way merge: every scan step selects
the earliest of

* **send** — the flow whose window opened earliest puts one segment on
  the serialized access link (``depart = max(t_ready, link_free) +
  1/link_pps``), appending a transmission record to its steering
  queue's arrival log,
* **claim** — the jax plane's batch-claim step over those dynamic
  queue logs: the earliest-feasible worker takes ``next_batch(backlog
  at t0)`` transmissions (backlog: arrivals counted on the arrival
  log), pays the claim overhead (+ a rare deschedule stall; the
  ``locked`` lock horizon and ``hybrid``'s argmax-over-backlogs victim
  selection both apply), scatters per-segment completions, and ORs the
  claimed ids into a **word-packed claim bitmap** (AtomicBitmap
  layout) that the multi-ring done-prefix kernel
  (:func:`repro.kernels.ops.done_prefix_packed`) checks for
  exactly-once delivery at the end,
* **ack** — delivery + ACK processing merged at ``completion +
  2*prop_delay`` (receiver and sender state are disjoint, so merging
  preserves event order): the receiver sets the segment's bit in a
  per-flow packed bitmap and the cumulative ACK is its **trailing-ones
  prefix** (the done-prefix trick, ``popcount((~w & -~w) - 1)`` per
  word); the sender runs NewReno with the two Linux behaviours of
  ``tcp.py`` — adaptive reordering threshold on DSACK and Eifel-style
  window undo on spurious retransmit — plus dup-ACK fast retransmit
  and slow-start/congestion-avoidance growth,
* **RTO** — when no send/claim/ack is pending and a flow is unfinished
  (the DES plane's ``on_idle`` sweep): reset the window and queue the
  hole for retransmission at ``t + rto``.

The scan is **batched-event**: consecutive events that cannot change
a policy decision coalesce into one step.  Sends go out in bursts of
up to ``send_burst`` segments (holes lowest-first, then new data) and
ACK-time selection is a hierarchical min — per-block mins over the
transmission record plus one top-level reduce, the claim-compacted
busy-span trick — while forwarder claims stay one-per-step so policy
semantics are untouched.  With ``tcp_params={"sack": True}`` (a
Python-static knob, bit-identical to absent when off) loss recovery
upgrades from the single-slot retransmit queue to a packed per-flow
**SACK scoreboard**: ACKs drain in batches up to the next send
candidate, holes are FACK-marked into a retransmission bitmap (one
cwnd cut per recovery episode, partial-ACK first-hole retransmit,
RFC 6675 pipe rule, shared DSACK/Eifel undo), ``loss_every`` injects
deterministic drop-once receiver loss, and per-lane ``pkt_budget``
clamps each lane's flow sizes (elephant/mice mixes).  The DES plane
mirrors every knob (``TcpSimConfig(sack=..., loss_every=...,
pkt_budget=...)``); ``tests/test_tcp_sack.py`` pins multi-hole
recovery and cross-plane FCT parity under loss.

The engine is claim-compacted in the :mod:`repro.core.jaxplane` sense:
the scan runs OUTSIDE the lane vmap in ``chunk``-step chunks, each
guarded by a scalar ``lax.cond`` on "every lane quiesced" (all flows
finished AND no send/claim/ack pending — trailing forwarder claims
keep a lane live so the exactly-once counters still settle), so the
generous event budget stops costing anything once the closed loops
drain; policies fuse as statically-bounded lane segments sharing one
compile; ``shards > 1`` partitions the lane axis across devices via
``jax.shard_map`` over :func:`repro.compat.lane_mesh`.
``engine="reference"`` keeps the pre-compaction per-lane scan over the
full budget — ``tests/test_compaction.py`` pins the compacted engine
bit-identical to it.

Parity with ``tcp.py`` is distributional (FCT percentiles, not RNG
draws) — see ``tests/test_tcpjax.py``; ``TcpSimConfig.queue_hints``
lets the DES plane steer with this plane's 32-bit hash so both planes
pin flows identically.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compat
from ..kernels import ops as kernel_ops
from . import record
from .jaxplane import (
    FaultParams,
    LaneParams,
    _attach_prefix,
    _broadcast_lanes,
    _call_fused,
    _chunked_scan,
    _pad_lanes,
    _resolve_policy,
    _resolve_shards,
    default_fault_params,
    default_lane_params,
    hash_u01,
    queue_heads,
    rows_arrived,
    steal_choice,
)

__all__ = [
    "TcpParams",
    "TcpLaneResult",
    "default_tcp_params",
    "tcp_lane_defaults",
    "run_tcp_lanes",
    "run_tcp_lanes_fused",
]

_FULL32 = jnp.uint32(0xFFFFFFFF)


class TcpParams(NamedTuple):
    """Per-lane TCP + path knobs (mirrors :class:`repro.core.tcp.TcpSimConfig`)."""

    service_mean: jnp.ndarray  # per-packet forwarding cost
    service_jitter: jnp.ndarray  # lognormal sigma on service
    prop_delay: jnp.ndarray  # one-way propagation
    link_pps: jnp.ndarray  # sender link rate (packets per unit time)
    init_cwnd: jnp.ndarray
    cubic_beta: jnp.ndarray  # multiplicative decrease
    rwnd: jnp.ndarray  # receive-window cap (packets)
    init_reorder_thresh: jnp.ndarray  # dup-ACK fast-retransmit threshold
    max_reorder_thresh: jnp.ndarray  # tcp_max_reordering analogue
    rto: jnp.ndarray  # coarse retransmission timer
    pkt_budget: jnp.ndarray  # per-lane cap on packets per flow (mice/elephant mixes)
    loss_every: jnp.ndarray  # drop the 1st arrival of every k-th segment (0 = off)
    loss_rate: jnp.ndarray  # random drop probability per segment (0.0 = off)
    loss_burst: jnp.ndarray  # mean loss-burst length in segments (1.0 = Bernoulli)


def default_tcp_params(**kw) -> dict:
    d = dict(
        service_mean=1.0,
        service_jitter=0.35,
        prop_delay=25.0,
        link_pps=0.85,
        init_cwnd=10,
        cubic_beta=0.7,
        rwnd=512,
        init_reorder_thresh=3,
        max_reorder_thresh=300,
        rto=5_000.0,
        pkt_budget=1 << 30,  # effectively uncapped; exact in fp32
        loss_every=0,
        loss_rate=0.0,
        loss_burst=1.0,
    )
    d.update(kw)
    return d


def tcp_lane_defaults(**kw) -> dict:
    """Claim-knob defaults matching ``TcpSimConfig`` (not the forwarder's)."""
    d = default_lane_params(
        claim_overhead=0.6, deschedule_prob=2e-4, deschedule_mean=150.0
    )
    d.update(kw)
    return d


class TcpLaneResult(NamedTuple):
    """Per-lane outputs of :func:`run_tcp_lanes`.

    ``active_steps`` / ``scan_steps`` count the event-scan steps taken
    before the lane went quiet and the steps its segment's scan ran,
    as on :class:`~repro.core.jaxplane.LaneResult`.
    """

    fct: jnp.ndarray  # [lanes, F] flow completion time (inf if unfinished)
    done: jnp.ndarray  # [lanes, F] flow finished within the step budget
    retransmissions: jnp.ndarray  # [lanes, F]
    spurious: jnp.ndarray  # [lanes, F] DSACK-detected spurious retransmits
    delivered: jnp.ndarray  # [lanes, F] receiver's contiguous delivered prefix
    sends: jnp.ndarray  # [lanes] transmissions put on the link
    batches: jnp.ndarray  # [lanes] forwarder claims
    items: jnp.ndarray  # [lanes] transmissions claimed
    deschedules: jnp.ndarray  # [lanes]
    claimed_popcount: jnp.ndarray  # [lanes] set bits in the claim bitmap
    claimed_prefix: jnp.ndarray  # [lanes] done prefix of that bitmap
    claimed_words: jnp.ndarray  # [lanes, n_words] that bitmap, uint32
    # -- scan counters (int32; what the engine ran, not what it simulated)
    active_steps: jnp.ndarray  # [lanes] steps taken before the lane was quiet
    scan_steps: jnp.ndarray  # [lanes] steps its segment's scan ran (the shard's)


def _trailing_ones(x: jnp.ndarray) -> jnp.ndarray:
    """Trailing-ones count of a uint32 word (no unpacking)."""
    y = ~x
    low = y & (jnp.uint32(0) - y)  # lowest set bit of ~x
    return jnp.where(
        x == _FULL32, jnp.int32(32), jax.lax.population_count(low - 1).astype(jnp.int32)
    )


def _recv_prefix(row: jnp.ndarray, m_bits: int) -> jnp.ndarray:
    """Contiguous received prefix of one flow's packed bitmap row."""
    full = row == _FULL32
    idx = jnp.argmax(~full).astype(jnp.int32)  # first not-full word
    bits = idx * 32 + _trailing_ones(row[idx])
    bits = jnp.where(jnp.all(full), jnp.int32(row.shape[0] * 32), bits)
    return jnp.minimum(bits, jnp.int32(m_bits))


#: block width of the hierarchical ACK-time min (per-block mins + one
#: top-level reduce instead of a flat argmin over the whole tx budget)
_ABLK = 32


def _popcnt_rows(words: jnp.ndarray) -> jnp.ndarray:
    """Set-bit count per packed row ([..., mw] -> [...] int32)."""
    return jnp.sum(jax.lax.population_count(words), axis=-1).astype(jnp.int32)


def _high_seq(row: jnp.ndarray) -> jnp.ndarray:
    """Highest set bit index of one packed row (-1 when empty)."""
    nz = row != 0
    mw = row.shape[0]
    widx = jnp.int32(mw - 1) - jnp.argmax(nz[::-1]).astype(jnp.int32)
    w = row[widx]
    w = w | (w >> 1)
    w = w | (w >> 2)
    w = w | (w >> 4)
    w = w | (w >> 8)
    w = w | (w >> 16)
    hb = jax.lax.population_count(w).astype(jnp.int32) - 1
    return jnp.where(jnp.any(nz), widx * 32 + hb, jnp.int32(-1))


def _bit_range(lo: jnp.ndarray, hi: jnp.ndarray, mw: int) -> jnp.ndarray:
    """Packed mask with bits ``lo..hi`` (inclusive) set; empty if hi < lo."""
    base = jnp.arange(mw, dtype=jnp.int32) * 32
    lo_rel = jnp.clip(lo - base, 0, 32)
    hi_rel = jnp.clip(hi + 1 - base, 0, 32)
    n = jnp.clip(hi_rel - lo_rel, 0, 32)
    body = jnp.where(
        n >= 32,
        _FULL32,
        jnp.left_shift(jnp.uint32(1), n.astype(jnp.uint32)) - 1,
    )
    out = jnp.left_shift(body, lo_rel.astype(jnp.uint32))
    return jnp.where(n > 0, out, jnp.uint32(0))


def _tcp_setup(tcp: TcpParams, seed, tx_budget: int, n_steps: int):
    """Per-lane draws for the closed-loop scan (service + stall streams)."""
    key = jax.random.PRNGKey(seed)
    kv, ku, ke = jax.random.split(key, 3)
    sj = tcp.service_jitter
    mu = jnp.log(tcp.service_mean) - sj**2 / 2
    svc = jnp.exp(jax.random.normal(kv, (tx_budget,)) * sj + mu).astype(jnp.float32)
    svc_pad = jnp.concatenate([svc, jnp.zeros(1, jnp.float32)])
    u_desch = jax.random.uniform(ku, (n_steps,))
    stalls = jax.random.exponential(ke, (n_steps,)).astype(jnp.float32)
    # counter-RNG key for the random-loss process (faults.hash_u01
    # mirror): keyed on the lane seed so the DES plane reproduces the
    # exact drop schedule from TcpSimConfig.seed
    lseed = jnp.asarray(seed, jnp.uint32)
    return dict(svc_pad=svc_pad, u=u_desch, stalls=stalls, lseed=lseed)


def _tcp_state0(
    lanes: int,
    tcp: TcpParams,
    t_start,
    n_flows: int,
    max_pkts: int,
    n_workers: int,
    max_batch: int,
    tx_budget: int,
    sack: bool,
    send_burst: int,
):
    """Initial closed-loop state, built directly on the lane axis."""
    f_cnt, w_cnt, mb, t_budget = n_flows, n_workers, max_batch, tx_budget
    sb = send_burst
    mw = (max_pkts + 31) // 32  # receiver bitmap words per flow
    tw = (t_budget + 31) // 32  # claim bitmap words
    nbk = (t_budget + 31) // _ABLK  # hierarchical-min ack blocks
    ts_pad = jnp.concatenate(
        [t_start.astype(jnp.float32), jnp.full(1, jnp.inf, jnp.float32)]
    )

    def full(shape, val, dtype):
        return jnp.full((lanes,) + shape, val, dtype)

    # the SACK scoreboard only exists on SACK segments: rtxp holds the
    # holes still awaiting retransmission, rtxd the ones already resent
    # and not yet cumulatively acked, rec_pt the recovery point (one
    # window cut per recovery episode)
    extra = (
        dict(
            rtxp=full((f_cnt + 1, mw), 0, jnp.uint32),
            rtxd=full((f_cnt + 1, mw), 0, jnp.uint32),
            in_rec=full((f_cnt + 1,), False, bool),
            rec_pt=full((f_cnt + 1,), -1, jnp.int32),
        )
        if sack
        else {}
    )
    return dict(
        **extra,
        # sender, per flow (+dump slot)
        cwnd=jnp.broadcast_to(
            tcp.init_cwnd[:, None].astype(jnp.float32), (lanes, f_cnt + 1)
        ),
        ssthresh=full((f_cnt + 1,), jnp.inf, jnp.float32),
        next_seq=full((f_cnt + 1,), 0, jnp.int32),
        high_ack=full((f_cnt + 1,), -1, jnp.int32),
        dup=full((f_cnt + 1,), 0, jnp.int32),
        infl=full((f_cnt + 1,), 0, jnp.int32),
        retx=full((f_cnt + 1,), 0, jnp.int32),
        spur=full((f_cnt + 1,), 0, jnp.int32),
        reo=jnp.broadcast_to(
            tcp.init_reorder_thresh[:, None].astype(jnp.int32), (lanes, f_cnt + 1)
        ),
        cwnd_before=full((f_cnt + 1,), 0, jnp.float32),
        last_retx=full((f_cnt + 1,), -1, jnp.int32),
        pend=full((f_cnt + 1,), -1, jnp.int32),  # single-slot retx queue
        done=full((f_cnt + 1,), False, bool),
        t_done=full((f_cnt + 1,), 0, jnp.float32),
        t_ready=jnp.broadcast_to(ts_pad, (lanes, f_cnt + 1)),
        # receiver, per flow: packed seen-bitmap + its contiguous
        # prefix, plus the drop-once bitmap of the loss injector
        rwords=full((f_cnt + 1, mw), 0, jnp.uint32),
        dwords=full((f_cnt + 1, mw), 0, jnp.uint32),
        # access link + transmission records (txf/txs carry sb blend
        # slack past the budget; tack pads to whole _ABLK blocks)
        link_free=full((), 0, jnp.float32),
        nsend=full((), 0, jnp.int32),
        txf=full((t_budget + sb,), 0, jnp.int32),
        txs=full((t_budget + sb,), 0, jnp.int32),
        tack=full((nbk * _ABLK + 1,), jnp.inf, jnp.float32),
        # forwarder: per-queue arrival logs + batch-claim state
        qidx=full((w_cnt + 1, t_budget + max(mb, sb)), t_budget, jnp.int32),
        qarr=full((w_cnt + 1, t_budget + sb), jnp.inf, jnp.float32),
        qapp=full((w_cnt + 1,), 0, jnp.int32),
        qptr=full((w_cnt,), 0, jnp.int32),
        freet=full((w_cnt,), 0, jnp.float32),
        lockt=full((), 0, jnp.float32),
        words=full((tw + 1,), 0, jnp.uint32),
        batches=full((), 0, jnp.int32),
        items=full((), 0, jnp.int32),
        deschs=full((), 0, jnp.int32),
        t_now=full((), 0, jnp.float32),
        quiet=full((), False, bool),
    )


def _tcp_step(
    policy,
    lp: LaneParams,
    tcp: TcpParams,
    consts,
    qid_flow,
    worker_queue,
    n_flows: int,
    max_pkts: int,
    n_workers: int,
    max_batch: int,
    tx_budget: int,
    sack: bool,
    send_burst: int,
    st,
    xs,
):
    """One batched-event step on one lane (shared by both engines).

    Each scan iteration retires a RUN of events, not one: a send puts a
    whole window-burst on the link in one step, and on SACK segments an
    ack step drains every ack that matures before the next send
    decision (acks commute with claims — disjoint state, and a claim
    only schedules ack times later than its own start — so the send
    candidate is the only ordering barrier).  Claims stay one per step:
    they ARE the policy decisions the batching must not blur.
    """
    f_cnt, w_cnt, mb, t_budget = n_flows, n_workers, max_batch, tx_budget
    sb = send_burst
    tw = (t_budget + 31) // 32
    mw = (max_pkts + 31) // 32
    nbk = (t_budget + 31) // _ABLK
    svc_pad = consts["svc_pad"]
    neff = consts["neff"]  # [F+1] per-lane effective flow sizes
    spacing = 1.0 / tcp.link_pps
    beta = tcp.cubic_beta
    max_reo = tcp.max_reorder_thresh.astype(jnp.int32)
    u, stall_draw = xs
    inf = jnp.float32(jnp.inf)
    frng = jnp.arange(f_cnt + 1)

    # ---- candidate event times ------------------------------------
    wnd = jnp.minimum(st["cwnd"], tcp.rwnd).astype(jnp.int32)
    if sack:
        has_rtx = jnp.any(st["rtxp"] != 0, axis=-1)
    else:
        has_rtx = st["pend"] >= 0
    can_send = (
        ~st["done"]
        & (st["infl"] < wnd)
        & (has_rtx | (st["next_seq"] < neff))
        & (st["nsend"] < t_budget)
    )
    tsf = jnp.where(can_send, st["t_ready"], inf)
    f_sel = jnp.argmin(tsf).astype(jnp.int32)
    t_send = jnp.where(
        jnp.isfinite(tsf[f_sel]), jnp.maximum(tsf[f_sel], st["link_free"]), inf
    )

    heads = queue_heads(st["qarr"][:w_cnt], st["qptr"])
    if policy.steals:
        arr_next = jnp.broadcast_to(jnp.min(heads), (w_cnt,))
    else:
        arr_next = heads[worker_queue]
    t_cand = jnp.maximum(st["freet"], arr_next)
    if policy.uses_lock:
        t_cand = jnp.maximum(t_cand, st["lockt"])
    # fault plane: a worker whose next claim would land at/after its
    # crash time is dead — crash-between-claims semantics (its queue
    # strands; stealing peers adopt the backlog, static-steer flows RTO
    # into the hole until the budget ends and report done=False)
    t_cand = jnp.where(t_cand >= consts["crash_w"], inf, t_cand)
    w_sel = jnp.argmin(t_cand).astype(jnp.int32)
    t_claim = t_cand[w_sel]

    # hierarchical ACK-time min: per-block mins + one top-level reduce
    # (the claim-compacted busy-span trick).  Recomputed wholesale each
    # step: on the CPU backend one fused [nbk, 32] reshape-min beats
    # carrying the block mins in state and patching them with
    # scatter-min / dynamic-slice upkeep (measured ~40% slower on the
    # full TCP grid), and the two-level argmin still halves the
    # selection cost vs a flat scan of the whole tx budget
    tackb = jnp.min(st["tack"][: nbk * _ABLK].reshape(nbk, _ABLK), axis=1)
    b_sel = jnp.argmin(tackb).astype(jnp.int32)
    t_ack = tackb[b_sel]

    live = ~st["done"] & (neff > 0)
    idle = ~(jnp.isfinite(t_send) | jnp.isfinite(t_claim) | jnp.isfinite(t_ack))
    # the DES plane's on_idle hook: the sweep RESETS state at the
    # idle instant and schedules the resend at t + rto (the rto
    # wait lives in t_ready below, not in this event's time)
    t_rto = jnp.where(jnp.any(live) & idle, st["t_now"], inf)

    times = jnp.stack([t_send, t_claim, t_ack, t_rto])
    ev = jnp.argmin(times)
    t_ev = times[ev]
    act = jnp.isfinite(t_ev)
    st["t_now"] = jnp.where(act, t_ev, st["t_now"])
    ms = act & (ev == 0)
    mc = act & (ev == 1)
    ma = act & (ev == 2)
    mr = act & (ev == 3)

    # once every flow finished AND no send/claim/ack is in flight the
    # lane can never change again — the chunked scan's exit signal
    st["quiet"] = ~jnp.any(live) & idle

    # ---- send: a whole window-burst onto the link in ONE step -----
    # retransmission holes go first (lowest-seq first), then new data,
    # exactly the DES plane's try_send drain order; departures chain at
    # link spacing so a burst equals sb single-send events back to back
    fd = jnp.where(ms, f_sel, f_cnt)
    base = jnp.where(ms, t_send, st["link_free"])
    space = jnp.maximum(wnd[fd] - st["infl"][fd], 0)
    if sack:
        holes = kernel_ops.first_set_bits(st["rtxp"][fd], sb)  # [sb]
        nh = jnp.sum(holes >= 0).astype(jnp.int32)
    else:
        nh = (st["pend"][fd] >= 0).astype(jnp.int32)
        holes = jnp.where(
            jnp.arange(sb, dtype=jnp.int32) == 0, st["pend"][fd], -1
        )
    fresh = jnp.maximum(neff[fd] - st["next_seq"][fd], 0)
    room = t_budget - st["nsend"]
    n_take = jnp.minimum(
        jnp.minimum(space, nh + fresh), jnp.minimum(room, sb)
    ).astype(jnp.int32)
    n_take = jnp.where(ms, n_take, 0)
    ii = jnp.arange(sb, dtype=jnp.int32)
    take = ii < n_take
    n_rtx = jnp.minimum(nh, n_take)
    is_rtx = ii < n_rtx
    seqs = jnp.where(is_rtx, holes, st["next_seq"][fd] + ii - nh)
    st["next_seq"] = st["next_seq"].at[fd].add(n_take - n_rtx)
    st["infl"] = st["infl"].at[fd].add(n_take)
    if sack:
        # move the retransmitted holes rtxp -> rtxd (scoreboard):
        # distinct bits, so an add-scatter builds the delta safely
        wi_h = jnp.where(is_rtx, holes >> 5, mw)
        bit_h = jnp.left_shift(jnp.uint32(1), (holes & 31).astype(jnp.uint32))
        dh = (
            jnp.zeros(mw + 1, jnp.uint32)
            .at[wi_h]
            .add(jnp.where(is_rtx, bit_h, jnp.uint32(0)))[:mw]
        )
        st["rtxp"] = st["rtxp"].at[fd].set(st["rtxp"][fd] & ~dh)
        st["rtxd"] = st["rtxd"].at[fd].set(st["rtxd"][fd] | dh)
    else:
        st["pend"] = st["pend"].at[fd].set(
            jnp.where(n_rtx > 0, -1, st["pend"][fd])
        )
    departs = base + spacing * (ii + 1).astype(jnp.float32)
    st["link_free"] = jnp.where(
        ms, base + spacing * n_take.astype(jnp.float32), st["link_free"]
    )
    # contiguous masked writes: blend the burst into the tx records
    # and the steering queue's arrival log via dynamic slices
    at0 = st["nsend"]
    cur_f = jax.lax.dynamic_slice(st["txf"], (at0,), (sb,))
    cur_s = jax.lax.dynamic_slice(st["txs"], (at0,), (sb,))
    st["txf"] = jax.lax.dynamic_update_slice(
        st["txf"], jnp.where(take, fd, cur_f), (at0,)
    )
    st["txs"] = jax.lax.dynamic_update_slice(
        st["txs"], jnp.where(take, seqs, cur_s), (at0,)
    )
    st["nsend"] = at0 + n_take
    row = jnp.where(ms, qid_flow[f_sel], w_cnt)
    pos = st["qapp"][row]
    cur_i = jax.lax.dynamic_slice(st["qidx"], (row, pos), (1, sb))[0]
    cur_a = jax.lax.dynamic_slice(st["qarr"], (row, pos), (1, sb))[0]
    st["qidx"] = jax.lax.dynamic_update_slice(
        st["qidx"], jnp.where(take, at0 + ii, cur_i)[None], (row, pos)
    )
    st["qarr"] = jax.lax.dynamic_update_slice(
        st["qarr"], jnp.where(take, departs + tcp.prop_delay, cur_a)[None], (row, pos)
    )
    st["qapp"] = st["qapp"].at[row].add(n_take)

    # ---- claim: the jax plane's batch-claim step on dynamic logs --
    t0 = jnp.where(mc, t_claim, 0.0)
    if policy.steals:
        q, backlog_q = steal_choice(
            st["qarr"][:w_cnt], st["qptr"], worker_queue[w_sel], t0
        )
        q = q.astype(jnp.int32)
        backlog = backlog_q[q]
    elif policy.shared:
        q = jnp.int32(0)
        # a binary search, not ``row_arrived``: this log is carried and
        # written every step, and a read of its whole row kept it out of
        # the TPU's fast memory, which made each shared-queue step of a
        # 144-lane TCP grid 3.8 % slower on a TPU v5e
        n_arrived = jnp.searchsorted(st["qarr"][0], t0, side="right")
        backlog = n_arrived.astype(jnp.int32) - st["qptr"][0]
    else:
        q = worker_queue[w_sel]
        backlog = rows_arrived(st["qarr"][:w_cnt], t0)[q] - st["qptr"][q]
    k = policy.next_batch(backlog, lp, w_cnt)
    k = jnp.clip(k, 1, jnp.minimum(backlog, mb))
    k = jnp.where(mc, k, 0)
    desch = mc & (u < lp.deschedule_prob)
    stall_t = jnp.where(desch, stall_draw * lp.deschedule_mean, 0.0)
    t1 = t0 + lp.claim_overhead + stall_t
    g = jax.lax.dynamic_slice(st["qidx"], (q, st["qptr"][q]), (1, mb))[0]
    valid = jnp.arange(mb) < k
    gj = jnp.where(valid, g, t_budget)
    # straggler inflation (exact ×1.0 identity on fault-free lanes)
    sv = jnp.where(valid, svc_pad[gj], 0.0) * consts["slow_w"][w_sel]
    comp = t1 + jnp.cumsum(sv)
    tack_v = jnp.where(valid, comp + 2 * tcp.prop_delay, inf)
    st["tack"] = st["tack"].at[gj].set(tack_v)
    t_end = t1 + jnp.sum(sv)
    st["freet"] = st["freet"].at[w_sel].set(jnp.where(mc, t_end, st["freet"][w_sel]))
    if policy.uses_lock:
        st["lockt"] = jnp.where(mc, t1, st["lockt"])
    st["qptr"] = st["qptr"].at[q].add(k)
    widx = jnp.where(valid, gj >> 5, tw)
    bit = jnp.left_shift(jnp.uint32(1), (gj & 31).astype(jnp.uint32))
    delta = (
        jnp.zeros(tw + 1, dtype=jnp.uint32)
        .at[widx]
        .add(jnp.where(valid, bit, jnp.uint32(0)))
    )
    st["words"] = st["words"] | delta
    st["batches"] = st["batches"] + mc.astype(jnp.int32)
    st["items"] = st["items"] + k
    st["deschs"] = st["deschs"] + desch.astype(jnp.int32)

    # ---- ack: delivery + ACK processing ---------------------------
    li = tcp.loss_every.astype(jnp.int32)
    lim = jnp.maximum(li, 1)
    if not sack:
        # per-event path: consume the single earliest ack (selected
        # hierarchically: top block, then argmin inside that block)
        blk = jax.lax.dynamic_slice(st["tack"], (b_sel * _ABLK,), (_ABLK,))
        j_sel = b_sel * _ABLK + jnp.argmin(blk).astype(jnp.int32)
        jad = jnp.where(ma, j_sel, t_budget)
        fa = st["txf"][jad]
        sa = st["txs"][jad]
        st["tack"] = st["tack"].at[jad].set(inf)  # consume
        fad = jnp.where(ma, fa, f_cnt)
        t_a = jnp.where(ma, t_ack, 0.0)
        wi = sa >> 5
        bsh = (sa & 31).astype(jnp.uint32)
        bitv = jnp.left_shift(jnp.uint32(1), bsh)
        # loss injection: the receiver drops the FIRST arrival of every
        # loss_every-th segment, exactly once per seq (dwords bitmap);
        # a dropped segment produces no ACK — the event just vanishes.
        # The random process ORs in: a segment is loss-scheduled iff its
        # counter-hash (lane seed, flow, seq block) lands under
        # loss_rate; whole loss_burst-wide blocks share one draw, so
        # the marginal drop rate stays loss_rate while losses cluster
        # with mean burst length loss_burst (Gilbert-Elliott-style)
        sched = (li > 0) & ((sa + 1) % lim == 0)
        lb = jnp.maximum(tcp.loss_burst.astype(jnp.int32), 1)
        u_loss = hash_u01(consts["lseed"], fa, sa // lb)
        sched = sched | (u_loss < tcp.loss_rate)
        seen_d = (st["dwords"][fad, wi] & bitv) != 0
        drop = ma & sched & ~seen_d
        st["dwords"] = (
            st["dwords"]
            .at[fad, wi]
            .set(st["dwords"][fad, wi] | jnp.where(drop, bitv, jnp.uint32(0)))
        )
        old_w = st["rwords"][fad, wi]
        dup_seg = (old_w >> bsh) & 1 == 1  # DSACK: receiver saw it before
        st["rwords"] = (
            st["rwords"]
            .at[fad, wi]
            .set(old_w | jnp.where(drop, jnp.uint32(0), bitv))
        )
        pref = _recv_prefix(st["rwords"][fad], max_pkts)
        ackno = pref - 1  # cumulative ACK == received prefix - 1

        alive = ma & ~drop & ~st["done"][fad]
        # spurious retransmit: raise the reordering threshold + Eifel undo
        dsk = alive & dup_seg
        st["spur"] = st["spur"].at[fad].add(dsk)
        st["reo"] = st["reo"].at[fad].set(
            jnp.where(dsk, jnp.minimum(st["reo"][fad] + 4, max_reo), st["reo"][fad])
        )
        undo = dsk & (st["cwnd_before"][fad] > st["cwnd"][fad])
        st["cwnd"] = st["cwnd"].at[fad].set(
            jnp.where(undo, st["cwnd_before"][fad], st["cwnd"][fad])
        )
        # cumulative advance: window growth + completion check
        adv = alive & (ackno > st["high_ack"][fad])
        newly = (ackno - st["high_ack"][fad]).astype(jnp.float32)
        st["infl"] = st["infl"].at[fad].set(
            jnp.where(
                adv,
                jnp.maximum(0, st["infl"][fad] - (ackno - st["high_ack"][fad])),
                st["infl"][fad],
            )
        )
        cw = st["cwnd"][fad]
        growth = jnp.where(cw < st["ssthresh"][fad], newly, newly / cw)
        st["cwnd"] = st["cwnd"].at[fad].set(jnp.where(adv, cw + growth, cw))
        st["high_ack"] = st["high_ack"].at[fad].set(
            jnp.where(adv, ackno, st["high_ack"][fad])
        )
        done_now = adv & (ackno >= neff[fad] - 1)
        # a masked OR, not a scatter: on a TPU, scatters into bool
        # arrays of many lanes lose updates
        st["done"] = st["done"] | ((frng == fad) & done_now)
        st["t_done"] = st["t_done"].at[fad].set(
            jnp.where(done_now, t_a, st["t_done"][fad])
        )
        # dup-ACK path: fast retransmit at the adaptive threshold
        dupinc = alive & ~adv & ~dup_seg
        dnew = st["dup"][fad] + 1
        fire = dupinc & (dnew >= st["reo"][fad])
        missing = st["high_ack"][fad] + 1
        do_rtx = (
            fire
            & (missing < neff[fad])
            & (missing != st["last_retx"][fad])
            & (st["pend"][fad] < 0)
        )
        st["pend"] = st["pend"].at[fad].set(
            jnp.where(do_rtx, missing, st["pend"][fad])
        )
        st["retx"] = st["retx"].at[fad].add(do_rtx)
        st["last_retx"] = st["last_retx"].at[fad].set(
            jnp.where(do_rtx, missing, st["last_retx"][fad])
        )
        st["infl"] = st["infl"].at[fad].set(
            jnp.where(do_rtx, jnp.maximum(0, st["infl"][fad] - 1), st["infl"][fad])
        )
        cw2 = st["cwnd"][fad]
        ss_cut = jnp.maximum(2.0, cw2 * beta)
        st["cwnd_before"] = st["cwnd_before"].at[fad].set(
            jnp.where(do_rtx, cw2, st["cwnd_before"][fad])
        )
        st["ssthresh"] = st["ssthresh"].at[fad].set(
            jnp.where(do_rtx, ss_cut, st["ssthresh"][fad])
        )
        st["cwnd"] = st["cwnd"].at[fad].set(jnp.where(do_rtx, ss_cut, cw2))
        st["dup"] = st["dup"].at[fad].set(
            jnp.where(adv | fire, 0, jnp.where(dupinc, dnew, st["dup"][fad]))
        )
        # the window may have opened: the flow can send again at t_a
        st["t_ready"] = st["t_ready"].at[fad].set(
            jnp.where(alive & ~done_now, t_a, st["t_ready"][fad])
        )
    else:
        # batched path: retire EVERY ack maturing before the next send
        # decision in one masked pass.  All receiver/sender updates
        # below are order-free per flow: OR-scatter of received bits,
        # prefix from the final bitmap, duplicate count as (arrivals -
        # newly set bits), aggregate window growth, scatter-min/max
        # for t_ready / completion time
        t_barrier = jnp.where(ma, jnp.maximum(t_send, t_ack), -inf)
        ta_j = st["tack"][:t_budget]
        m = (ta_j <= t_barrier) & jnp.isfinite(ta_j)
        fa_j = st["txf"][:t_budget]
        sa_j = st["txs"][:t_budget]
        fad_j = jnp.where(m, fa_j, f_cnt)
        sa_c = jnp.clip(sa_j, 0, mw * 32 - 1)
        wi_j = sa_c >> 5
        bit_j = jnp.left_shift(jnp.uint32(1), (sa_c & 31).astype(jnp.uint32))
        # loss injection: among same-seq copies in one batch only the
        # EARLIEST undropped arrival is eligible to drop (DES order);
        # random loss ORs into the schedule exactly as on the per-event
        # path (same counter-hash, same block-burst semantics)
        sched_j = (li > 0) & ((sa_j + 1) % lim == 0)
        lb_j = jnp.maximum(tcp.loss_burst.astype(jnp.int32), 1)
        u_loss_j = hash_u01(consts["lseed"], fa_j, sa_j // lb_j)
        sched_j = sched_j | (u_loss_j < tcp.loss_rate)
        seen_j = (st["dwords"][fad_j, wi_j] & bit_j) != 0
        cand_j = m & sched_j & ~seen_j
        tmin_seq = (
            jnp.full((f_cnt + 1, mw * 32), inf)
            .at[fad_j, sa_c]
            .min(jnp.where(cand_j, ta_j, inf))
        )
        drop_j = cand_j & (ta_j <= tmin_seq[fad_j, sa_c])
        deliv_j = m & ~drop_j
        # 0/1 staging + pack_bits_u32 gives an idempotent OR-scatter
        # (scatter-max) even with duplicate (flow, seq) pairs; int32,
        # since on a TPU scatters into bool arrays lose updates
        stage = (
            jnp.zeros((f_cnt + 1, mw * 32), jnp.int32)
            .at[fad_j, sa_c]
            .max(deliv_j.astype(jnp.int32))
        )
        old_rw = st["rwords"]
        new_rw = old_rw | kernel_ops.pack_bits_u32(stage)
        st["rwords"] = new_rw
        dstage = (
            jnp.zeros((f_cnt + 1, mw * 32), jnp.int32)
            .at[fad_j, sa_c]
            .max(drop_j.astype(jnp.int32))
        )
        st["dwords"] = st["dwords"] | kernel_ops.pack_bits_u32(dstage)
        st["tack"] = st["tack"].at[:t_budget].set(jnp.where(m, inf, ta_j))
        # per-flow batch aggregates
        arr_f = jnp.zeros(f_cnt + 1, jnp.int32).at[fad_j].add(deliv_j)
        tmin_f = (
            jnp.full(f_cnt + 1, inf).at[fad_j].min(jnp.where(deliv_j, ta_j, inf))
        )
        tmax_f = (
            jnp.full(f_cnt + 1, -inf)
            .at[fad_j]
            .max(jnp.where(deliv_j, ta_j, -inf))
        )
        pref_f = jax.vmap(lambda r: _recv_prefix(r, max_pkts))(new_rw)
        ackno_f = pref_f - 1
        alive_f = ~st["done"]  # pre-batch completion state
        # DSACK: every arrival that set no new bit is a duplicate
        dup_f = jnp.maximum(arr_f - (_popcnt_rows(new_rw) - _popcnt_rows(old_rw)), 0)
        dsk_f = alive_f & (dup_f > 0)
        st["spur"] = st["spur"] + jnp.where(dsk_f, dup_f, 0)
        st["reo"] = jnp.where(
            dsk_f, jnp.minimum(st["reo"] + 4 * dup_f, max_reo), st["reo"]
        )
        undo_f = dsk_f & (st["cwnd_before"] > st["cwnd"])
        st["cwnd"] = jnp.where(undo_f, st["cwnd_before"], st["cwnd"])
        # cumulative advance (aggregated growth; no growth in recovery)
        adv_f = alive_f & (ackno_f > st["high_ack"])
        newly_f = (ackno_f - st["high_ack"]).astype(jnp.float32)
        grow_f = adv_f & ~st["in_rec"]
        growth = jnp.where(st["cwnd"] < st["ssthresh"], newly_f, newly_f / st["cwnd"])
        st["cwnd"] = jnp.where(grow_f, st["cwnd"] + growth, st["cwnd"])
        st["high_ack"] = jnp.where(adv_f, ackno_f, st["high_ack"])
        done_now_f = adv_f & (ackno_f >= neff - 1)
        st["done"] = st["done"] | done_now_f
        st["t_done"] = jnp.where(done_now_f, tmax_f, st["t_done"])
        # scoreboard upkeep: drop marks below the cumulative ack, then
        # close the recovery episode once the ack passes its point
        pmask = jax.vmap(lambda hi: _bit_range(jnp.int32(0), hi, mw))(
            st["high_ack"]
        )
        st["rtxp"] = st["rtxp"] & ~pmask
        st["rtxd"] = st["rtxd"] & ~pmask
        exit_f = adv_f & st["in_rec"] & (ackno_f >= st["rec_pt"])
        st["rtxd"] = jnp.where(exit_f[:, None], jnp.uint32(0), st["rtxd"])
        st["in_rec"] = st["in_rec"] & ~exit_f
        # FACK-style loss marking: a hole is lost once the highest
        # SACKed seq runs reorder_thresh past it; mark all such holes
        # (multi-hole recovery) with ONE window cut per episode
        hs_f = jax.vmap(_high_seq)(new_rw)
        cut_hi = jnp.minimum(hs_f - st["reo"], neff - 1)
        lost_f = jax.vmap(lambda lo, hi: _bit_range(lo, hi, mw))(pref_f, cut_hi)
        lost_f = lost_f & ~new_rw & ~st["rtxp"] & ~st["rtxd"]
        n_lost = _popcnt_rows(lost_f)
        mark_f = ma & alive_f & ~st["done"] & (n_lost > 0)
        enter_f = mark_f & ~st["in_rec"]
        st["retx"] = st["retx"] + jnp.where(mark_f, n_lost, 0)
        st["rtxp"] = jnp.where(mark_f[:, None], st["rtxp"] | lost_f, st["rtxp"])
        cut = jnp.maximum(2.0, st["cwnd"] * beta)
        st["cwnd_before"] = jnp.where(enter_f, st["cwnd"], st["cwnd_before"])
        st["ssthresh"] = jnp.where(enter_f, cut, st["ssthresh"])
        st["cwnd"] = jnp.where(enter_f, cut, st["cwnd"])
        st["rec_pt"] = jnp.where(enter_f, st["next_seq"] - 1, st["rec_pt"])
        st["in_rec"] = st["in_rec"] | enter_f
        # partial ACK inside recovery: retransmit the first hole now
        fh = pref_f
        part_f = (
            ma
            & adv_f
            & st["in_rec"]
            & (ackno_f < st["rec_pt"])
            & (fh < neff)
        )
        fh_wi = jnp.clip(fh >> 5, 0, mw - 1)
        fh_bit = jnp.left_shift(jnp.uint32(1), (fh & 31).astype(jnp.uint32))
        board = jnp.take_along_axis(
            st["rtxp"] | st["rtxd"], fh_wi[:, None], axis=1
        )[:, 0]
        pr_f = part_f & ((board & fh_bit) == 0)
        cur_w = jnp.take_along_axis(st["rtxp"], fh_wi[:, None], axis=1)[:, 0]
        st["rtxp"] = st["rtxp"].at[frng, fh_wi].set(
            cur_w | jnp.where(pr_f, fh_bit, jnp.uint32(0))
        )
        st["retx"] = st["retx"] + pr_f
        # RFC 6675 pipe: in flight = sent segments above the cumulative
        # ack that are neither SACKed nor marked lost (a retransmitted
        # hole re-counts via its cleared rtxp bit until SACKed), so
        # SACKed bytes free window space instead of wedging recovery
        region = jax.vmap(lambda lo, hi: _bit_range(lo, hi, mw))(
            pref_f, st["next_seq"] - 1
        )
        pipe = _popcnt_rows(region & ~new_rw & ~st["rtxp"])
        st["infl"] = jnp.where(ma, pipe, st["infl"])
        # the window may have opened at the earliest ack in the batch
        rdy_f = alive_f & ~st["done"] & jnp.isfinite(tmin_f)
        st["t_ready"] = jnp.where(rdy_f, tmin_f, st["t_ready"])

    # ---- RTO sweep: everything stalled, resend from the hole ------
    mrf = mr & live
    missing_r = st["high_ack"] + 1
    cond = mrf & (missing_r < neff)
    st["ssthresh"] = jnp.where(mrf, jnp.maximum(2.0, st["cwnd"] * beta), st["ssthresh"])
    st["cwnd"] = jnp.where(mrf, tcp.init_cwnd, st["cwnd"])
    st["infl"] = jnp.where(mrf, 0, st["infl"])
    if sack:
        # a timeout voids the whole scoreboard: retransmitted-unacked
        # marks are forgotten and just the first hole is re-marked
        st["rtxd"] = jnp.where(mrf[:, None], jnp.uint32(0), st["rtxd"])
        st["in_rec"] = st["in_rec"] & ~mrf
        mr_wi = jnp.clip(missing_r >> 5, 0, mw - 1)
        mr_bit = jnp.left_shift(jnp.uint32(1), (missing_r & 31).astype(jnp.uint32))
        cur_r = jnp.take_along_axis(st["rtxp"], mr_wi[:, None], axis=1)[:, 0]
        fresh_mark = cond & ((cur_r & mr_bit) == 0)
        st["retx"] = st["retx"] + fresh_mark
        st["rtxp"] = st["rtxp"].at[frng, mr_wi].set(
            cur_r | jnp.where(fresh_mark, mr_bit, jnp.uint32(0))
        )
    else:
        st["dup"] = jnp.where(mrf, 0, st["dup"])
        st["retx"] = st["retx"] + (cond & (st["pend"] != missing_r)).astype(jnp.int32)
        st["pend"] = jnp.where(cond, missing_r, st["pend"])
        st["last_retx"] = jnp.where(cond, missing_r, st["last_retx"])
    st["t_ready"] = jnp.where(mrf, st["t_now"] + tcp.rto, st["t_ready"])

    return st, None


def _tcp_outputs(st, consts, t_start, n_flows: int, max_pkts: int, tx_budget: int):
    f_cnt = n_flows
    tw = (tx_budget + 31) // 32
    done = st["done"][:, :f_cnt]
    fct = jnp.where(done, st["t_done"][:, :f_cnt] - t_start, jnp.inf)
    words = st["words"][:, :tw]
    pop = jnp.sum(jax.lax.population_count(words), axis=-1).astype(jnp.int32)
    pref = jax.vmap(jax.vmap(lambda r: _recv_prefix(r, max_pkts)))(
        st["rwords"][:, :f_cnt]
    )
    delivered = jnp.minimum(pref, consts["neff"][:, :f_cnt])
    return dict(
        fct=fct,
        done=done,
        retx=st["retx"][:, :f_cnt],
        spur=st["spur"][:, :f_cnt],
        delivered=delivered,
        sends=st["nsend"],
        batches=st["batches"],
        items=st["items"],
        deschs=st["deschs"],
        words=words,
        popcount=pop,
    )


def _tcp_core(
    blocks,
    pols,
    n_pkts,
    t_start,
    n_flows: int,
    max_pkts: int,
    n_workers: int,
    max_batch: int,
    tx_budget: int,
    s_pad: int,
    chunk: int,
    engine: str,
    sacks,
    send_burst: int,
):
    """Advance every lane of every policy segment through the closed
    loop; returns per-segment dicts of lane-axis arrays (safe to wrap
    in ``shard_map``)."""
    f_cnt, w_cnt = n_flows, n_workers
    n_pad = jnp.concatenate([n_pkts.astype(jnp.int32), jnp.zeros(1, jnp.int32)])
    outs = []
    seg_states, seg_steps, seg_consts = [], [], []
    for pol, sack, (lp, tcp, fparams, seeds) in zip(pols, sacks, blocks):
        with jax.named_scope(f"seg.{pol.name}"):
            lanes = seeds.shape[0]
            # NIC-side steering is static per flow (RSS hash / shared queue 0)
            qid_flow = pol.select_queue(jnp.arange(f_cnt, dtype=jnp.int32), w_cnt)
            qid_flow = jnp.concatenate([qid_flow, jnp.zeros(1, jnp.int32)])
            if pol.shared:
                worker_queue = jnp.zeros(w_cnt, dtype=jnp.int32)
            else:
                worker_queue = jnp.arange(w_cnt, dtype=jnp.int32)
            seg_steps.append(
                functools.partial(
                    _tcp_step,
                    pol,
                    qid_flow=qid_flow,
                    worker_queue=worker_queue,
                    n_flows=f_cnt,
                    max_pkts=max_pkts,
                    n_workers=w_cnt,
                    max_batch=max_batch,
                    tx_budget=tx_budget,
                    sack=sack,
                    send_burst=send_burst,
                )
            )
            consts = jax.vmap(
                functools.partial(_tcp_setup, tx_budget=tx_budget, n_steps=s_pad)
            )(tcp, seeds)
            # per-lane effective flow sizes: the packet-budget mask lets
            # one lane carry an elephant/mice mix over the shared layout
            pb = jnp.maximum(tcp.pkt_budget.astype(jnp.int32), 0)
            consts["neff"] = jnp.minimum(n_pad[None, :], pb[:, None])
            # per-worker fault axes [lanes, W]: crash horizon + service
            # slowdown (crash_t=+inf / straggler=1.0 on fault-free lanes)
            widx = jnp.arange(w_cnt, dtype=jnp.float32)
            consts["crash_w"] = jnp.where(
                widx[None, :] == fparams.crash_worker[:, None],
                fparams.crash_t[:, None],
                jnp.inf,
            ).astype(jnp.float32)
            consts["slow_w"] = jnp.where(
                widx[None, :] == fparams.straggler_worker[:, None],
                fparams.straggler[:, None],
                1.0,
            ).astype(jnp.float32)
            seg_consts.append(consts)
            seg_states.append(
                _tcp_state0(
                    lanes,
                    tcp,
                    t_start,
                    f_cnt,
                    max_pkts,
                    w_cnt,
                    max_batch,
                    tx_budget,
                    sack,
                    send_burst,
                )
            )

    def lane_done(st):
        return st["quiet"]

    finals = []
    if engine == "reference":
        for pol, (lp, tcp, _, _), st0, step, consts in zip(
            pols, blocks, seg_states, seg_steps, seg_consts
        ):

            def one_lane(lp_l, tcp_l, c_l, st_l, step=step):
                def body(carry, x):
                    s, active = carry
                    active = active + (~lane_done(s)).astype(jnp.int32)
                    return (step(lp_l, tcp_l, c_l, st=s, xs=x)[0], active), None

                xs = (c_l["u"], c_l["stalls"])
                (st, active), _ = jax.lax.scan(body, (st_l, jnp.int32(0)), xs)
                return st, active

            with jax.named_scope(f"seg.{pol.name}"), jax.named_scope("scan"):
                st, active = jax.vmap(one_lane)(lp, tcp, consts, st0)
            finals.append((st, active, jnp.full_like(active, s_pad)))
    elif engine == "compacted":
        # one specialized chunked scan PER policy segment, all inside
        # the one jitted call: each segment's lanes stop paying for the
        # event budget at their own quiesce point, and each step
        # compiles without the untaken policies' branches (a per-lane
        # flag dispatch was measured slower than static segmentation
        # here — the step is compute-bound at sweep lane counts)
        for pol, (lp, tcp, _, _), st0, step, consts in zip(
            pols, blocks, seg_states, seg_steps, seg_consts
        ):

            def body(carry, x, step=step, lp=lp, tcp=tcp, consts=consts):
                def one(lp_l, tcp_l, c_l, st_l, u_l, s_l):
                    return step(lp_l, tcp_l, c_l, st=st_l, xs=(u_l, s_l))[0]

                return jax.vmap(one)(lp, tcp, consts, carry, x[0], x[1]), ()

            with jax.named_scope(f"seg.{pol.name}"):
                xs = (consts["u"].T, consts["stalls"].T)
                with jax.named_scope("scan"):
                    st, _, active, scanned = _chunked_scan(
                        body, st0, xs, lane_done, chunk
                    )
            finals.append((st, active, scanned))
    else:
        raise ValueError(f"unknown engine {engine!r}")
    for pol, consts, (st, active, scanned) in zip(pols, seg_consts, finals):
        with jax.named_scope(f"seg.{pol.name}"), jax.named_scope("post_scan"):
            out = _tcp_outputs(st, consts, t_start, f_cnt, max_pkts, tx_budget)
        outs.append(dict(out, active_steps=active, scan_steps=scanned))
    return tuple(outs)


def _run_tcp_fused_impl(
    blocks,
    n_pkts,
    t_start,
    *,
    pols,
    n_flows: int,
    max_pkts: int,
    n_workers: int,
    max_batch: int,
    tx_budget: int,
    s_pad: int,
    chunk: int,
    n_shards: int,
    engine: str,
    sacks,
    send_burst: int,
    prefix_impl: str,
    prefix_interpret: bool,
):
    def core(blocks):
        outs = _tcp_core(
            blocks,
            n_pkts=n_pkts,
            t_start=t_start,
            pols=pols,
            n_flows=n_flows,
            max_pkts=max_pkts,
            n_workers=n_workers,
            max_batch=max_batch,
            tx_budget=tx_budget,
            s_pad=s_pad,
            chunk=chunk,
            engine=engine,
            sacks=sacks,
            send_burst=send_burst,
        )
        # exactly-once on the claim bitmap: every transmission put on the
        # link was claimed by exactly one batch (popcount == prefix == sends)
        return _attach_prefix(
            outs,
            tx_budget,
            jnp.concatenate([o["sends"] for o in outs], axis=0),
            impl=prefix_impl,
            interpret=prefix_interpret,
        )

    if n_shards > 1:
        spec = jax.sharding.PartitionSpec("lanes")
        core = jax.shard_map(
            core,
            mesh=compat.lane_mesh(n_shards),
            in_specs=(spec,),
            out_specs=spec,
            check_vma=False,
        )
    return tuple(
        TcpLaneResult(
            fct=o["fct"],
            done=o["done"],
            retransmissions=o["retx"],
            spurious=o["spur"],
            delivered=o["delivered"],
            sends=o["sends"],
            batches=o["batches"],
            items=o["items"],
            deschedules=o["deschs"],
            claimed_popcount=o["popcount"],
            claimed_prefix=o["prefix"],
            claimed_words=o["words"],
            active_steps=o["active_steps"],
            scan_steps=o["scan_steps"],
        )
        for o in core(blocks)
    )


_TCP_STATICS = (
    "pols",
    "n_flows",
    "max_pkts",
    "n_workers",
    "max_batch",
    "tx_budget",
    "s_pad",
    "chunk",
    "n_shards",
    "engine",
    "sacks",
    "send_burst",
    "prefix_impl",
    "prefix_interpret",
)


@functools.lru_cache(maxsize=None)
def _tcp_fused_jit(donate: bool):
    return jax.jit(
        _run_tcp_fused_impl,
        static_argnames=_TCP_STATICS,
        donate_argnums=(0,) if donate else (),
    )


def run_tcp_lanes_fused(
    requests,
    *,
    n_pkts=256,
    t_start=None,
    n_workers: int = 4,
    max_batch: int = 64,
    tx_budget: int | None = None,
    n_steps: int | None = None,
    engine: str = "compacted",
    chunk: int = 64,
    shards: int | str = 1,
    prefix_impl: str = "auto",
    prefix_interpret: bool = False,
    timings: dict | None = None,
):
    """Simulate every TCP lane of every request in ONE jitted call.

    ``requests`` is a sequence of dicts ``{"policy": name-or-JaxPolicy,
    "seeds": [...], "lane_params": {...}, "tcp_params": {...}}`` — one
    statically-bounded lane segment per request, all sharing the flow
    layout (``n_pkts`` / ``t_start``) and budgets.  Returns one
    :class:`TcpLaneResult` per request, in order.  ``tx_budget`` bounds
    total transmissions (originals + retransmits; default 9/8 of the
    packet total + 32) and ``n_steps`` the event budget — rounded up to
    a multiple of ``chunk`` so the quiesce short-circuit can skip whole
    chunks; flows that do not finish within them report ``done=False``
    and an infinite ``fct``.  ``shards`` / ``timings`` behave like
    :func:`repro.core.jaxplane.run_lanes_fused`, and so do its
    profiler spans ``repro.prepare`` / ``repro.dispatch`` and its entry
    in :mod:`repro.core.record`.
    """
    requests = list(requests)
    if not requests:
        raise ValueError("run_tcp_lanes_fused: empty request list")
    record.install()
    with jax.profiler.TraceAnnotation("repro.prepare"):
        args, static, orig_lanes = _tcp_fused_args(
            requests, n_pkts, t_start, tx_budget, n_steps, chunk, shards
        )
    static.update(
        n_workers=n_workers,
        max_batch=max_batch,
        engine=engine,
        prefix_impl=prefix_impl,
        prefix_interpret=prefix_interpret,
    )
    fn = _tcp_fused_jit(jax.default_backend() != "cpu")
    with jax.profiler.TraceAnnotation("repro.dispatch"):
        outs = _call_fused(fn, args, static, timings)
    return [
        jax.tree_util.tree_map(lambda a: a[:lanes], res)
        for res, lanes in zip(outs, orig_lanes)
    ]


def _tcp_fused_args(requests, n_pkts, t_start, tx_budget, n_steps, chunk, shards):
    """The lane blocks and flow layout of the fused call, its shape
    statics and each request's lane count (before padding)."""
    n_arr = np.atleast_1d(np.asarray(n_pkts, dtype=np.int32))
    f_cnt = int(n_arr.shape[0])
    max_pkts = int(n_arr.max())
    total = int(n_arr.sum())
    if t_start is None:
        t_start = np.zeros(f_cnt, dtype=np.float32)
    t_start = np.asarray(t_start, dtype=np.float32)
    if t_start.shape != (f_cnt,):
        raise ValueError(f"t_start shape {t_start.shape} != ({f_cnt},)")
    if tx_budget is None:
        tx_budget = total + total // 8 + 32
    if n_steps is None:
        n_steps = 3 * int(tx_budget) + f_cnt + 64
    chunk = max(1, int(chunk))
    s_pad = -(-int(n_steps) // chunk) * chunk
    n_shards = _resolve_shards(shards)

    pols, blocks, orig_lanes, sacks = [], [], [], []
    sb_seen = set()
    for req in requests:
        pol = _resolve_policy(req["policy"])
        seeds = jnp.asarray(np.asarray(req["seeds"], dtype=np.uint32))
        lanes = seeds.shape[0]
        lp = tcp_lane_defaults(**(req.get("lane_params") or {}))
        tp = default_tcp_params(**(req.get("tcp_params") or {}))
        # ``sack`` / ``send_burst`` are STATIC per segment (the SACK
        # scoreboard branch compiles only when asked for, keeping
        # SACK-off lanes IEEE-identical to the pre-SACK engine), so
        # they must be python scalars, not lane arrays
        sack_raw = tp.pop("sack", False)
        if not isinstance(sack_raw, (bool, int)) or isinstance(sack_raw, float):
            raise ValueError("tcp_params['sack'] must be a scalar bool (static)")
        sacks.append(bool(sack_raw))
        sb_raw = tp.pop("send_burst", None)
        if sb_raw is not None:
            if not isinstance(sb_raw, int) or isinstance(sb_raw, bool) or sb_raw < 1:
                raise ValueError(
                    "tcp_params['send_burst'] must be a positive int (static)"
                )
            sb_seen.add(sb_raw)
        # crash-between-claims + straggler only on this plane: claims
        # here never crash mid-batch, so the ``lease`` knob is accepted
        # for request-shape parity but has nothing to reclaim
        fp = default_fault_params(**(req.get("fault_params") or {}))
        unknown = set(lp) - set(LaneParams._fields)
        unknown |= set(tp) - set(TcpParams._fields)
        unknown |= set(fp) - set(FaultParams._fields)
        if unknown:
            raise ValueError(f"unknown sweep knobs: {sorted(unknown)}")
        params = LaneParams(*_broadcast_lanes(lp, LaneParams._fields, lanes))
        tcp_p = TcpParams(*_broadcast_lanes(tp, TcpParams._fields, lanes))
        fparams = FaultParams(*_broadcast_lanes(fp, FaultParams._fields, lanes))
        pad = (-lanes) % n_shards
        pols.append(pol)
        blocks.append(_pad_lanes((params, tcp_p, fparams, seeds), pad))
        orig_lanes.append(lanes)

    if len(sb_seen) > 1:
        raise ValueError(
            f"send_burst must agree across fused requests, got {sorted(sb_seen)}"
        )
    send_burst = sb_seen.pop() if sb_seen else 32
    static = dict(
        pols=tuple(pols),
        n_flows=f_cnt,
        max_pkts=max_pkts,
        tx_budget=int(tx_budget),
        s_pad=s_pad,
        chunk=chunk,
        n_shards=n_shards,
        sacks=tuple(sacks),
        send_burst=send_burst,
    )
    args = (tuple(blocks), jnp.asarray(n_arr), jnp.asarray(t_start))
    return args, static, orig_lanes


def run_tcp_lanes(
    policy: str,
    seeds,
    n_pkts=256,
    t_start=None,
    lane_params: dict | None = None,
    tcp_params: dict | None = None,
    fault_params: dict | None = None,
    n_workers: int = 4,
    max_batch: int = 64,
    tx_budget: int | None = None,
    n_steps: int | None = None,
    engine: str = "compacted",
    chunk: int = 64,
    shards: int | str = 1,
    prefix_impl: str = "auto",
    prefix_interpret: bool = False,
) -> TcpLaneResult:
    """Simulate every (policy-param, seed) TCP lane in one jitted call.

    ``n_pkts`` is the flow layout, shared by all lanes: an int (one
    flow) or a sequence of per-flow packet counts; ``t_start`` gives
    per-flow start times (default 0).  ``lane_params`` /
    ``tcp_params`` map knob names to scalars or [lanes] arrays exactly
    like :func:`repro.core.jaxplane.run_lanes`; ``seeds`` defines the
    lane count.  A single-segment wrapper over
    :func:`run_tcp_lanes_fused` — see there for the budget and
    ``engine`` / ``chunk`` / ``shards`` knobs.
    """
    return run_tcp_lanes_fused(
        [
            dict(
                policy=policy,
                seeds=seeds,
                lane_params=lane_params,
                tcp_params=tcp_params,
                fault_params=fault_params,
            )
        ],
        n_pkts=n_pkts,
        t_start=t_start,
        n_workers=n_workers,
        max_batch=max_batch,
        tx_budget=tx_budget,
        n_steps=n_steps,
        engine=engine,
        chunk=chunk,
        shards=shards,
        prefix_impl=prefix_impl,
        prefix_interpret=prefix_interpret,
    )[0]
