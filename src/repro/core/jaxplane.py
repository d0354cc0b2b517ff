"""Vectorized JAX execution plane: the registry's third simulator.

The DES plane (:mod:`repro.core.des`) evaluates one (policy, config,
seed) point per Python event loop — minutes of wall clock for a
registry-wide sweep.  This module re-states the same receive-side model
as a pure JAX program built around a **claim-compacted scan engine**:

* One scan step = one batch claim (the worker with the earliest
  feasible claim time takes ``next_batch(backlog)`` packets from its
  queue).  The step carries only O(workers) state — queue claim
  pointers, worker free times, a lock horizon and three counters — and
  emits a tiny :class:`ClaimRecord` ``(queue, start, size, t_claimed)``
  instead of scattering per-packet completion times through the carry.
* After the scan, ONE batched segment-style scatter reconstructs every
  packet's completion time from the claim records (scatter claim ids at
  their start ranks, forward-fill with ``cummax``, difference of
  per-queue service prefix sums), and the packed claim bitmap is packed
  from the claimed mask in one shot (:func:`repro.kernels.ops.
  pack_bits_u32`).
* The scan runs OUTSIDE the lane vmap in chunks of ``chunk`` steps,
  each chunk guarded by a scalar ``lax.cond`` on "every lane drained" —
  a real branch, so once all lanes are done the remaining claim budget
  costs nothing (the ``done`` short-circuit).  The claim budget is an
  upper bound on claim events; the sound default is ``n_packets``
  (every active claim takes >= 1 packet) and callers that know their
  load regime can pass a tighter ``claim_budget``.
* **Fusion**: :func:`run_lanes_fused` evaluates every requested policy
  in ONE jitted call — the lane axis is segmented per policy with
  static boundaries, each segment's step specialized to its
  :class:`JaxPolicy` (the static-segment equivalent of a ``lax.switch``
  over the policy table, without paying for the untaken branches on
  every lane), so a registry-wide sweep compiles and dispatches once
  instead of once per policy.
* **Sharding**: ``shards > 1`` partitions the lane axis across devices
  through ``jax.shard_map`` over :func:`repro.compat.lane_mesh`
  (each segment is padded to a multiple of the device count; CI
  exercises the path on CPU via ``--xla_force_host_platform_device_
  count``).  Lane-axis inputs are donated to the jit on backends that
  support aliasing, and the working set is dtype-audited: fp32
  completion vectors, uint32 packed bitmaps, int32 claim records.

``engine="reference"`` keeps the per-claim scan that writes each
claim's completion window inside the step (the pre-compaction
formulation): ``tests/test_compaction.py`` pins the compacted engine
bit-identical to it for every registry policy.

**Serving mode** (``serving=True``, used by :mod:`repro.core.
servingjax`): the same scan becomes an open-loop serving sweep.  Each
packet is one user request; :class:`ServingParams` adds per-lane
admission control (``admit_limit`` — a claiming worker sheds up to
``max_batch`` over-limit requests from the queue head before serving,
the dequeue-side drop of a real driver), an autoscaled worker pool
(worker ``w >= base_workers`` wakes only once its queue's unclaimed
backlog reaches ``(w - base_workers + 1) * scale_backlog`` — expressed
as a wake-time gate on the threshold-th unclaimed arrival so the
event-driven formulation stays exact), and a generation ``horizon``
(arrivals after it never happen: the open-loop reformulation of the
fixed ``n_packets`` budget — ``offered`` counts the arrivals that do).
Every serving knob is an exact IEEE identity at its ``+inf`` default
(the :class:`FaultParams` convention), so serving-mode lanes with
default knobs reproduce the classic engine's dynamics and the
compacted/reference bit-identity pin covers the serving step too.
SLO attainment (fraction of *offered* users whose sojourn meets
``slo_target``) and delivered-only latency percentiles are computed
in-graph.

Model semantics (matching the DES plane's dynamics, not its RNG stream
— parity is distributional, see ``tests/test_jaxplane.py``): packets
are pre-drawn per lane exactly like the scenario layers pre-draw them;
state per lane is per-queue claim pointers, per-worker free times and a
lock horizon (``locked`` only); ``hybrid`` steals couple queues through
instantaneous backlogs (arrivals counted at the claim instant).
Latency percentiles, the RFC-4737 Type-P-Reordered ratio / max
distance, and the exactly-once check (claim-bitmap popcount == done
prefix == items, via :func:`repro.kernels.ops.done_prefix_packed`) all
run in-graph.
"""

from __future__ import annotations

import functools
import math
import time
import warnings
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compat
from ..kernels import ops as kernel_ops
from . import record

__all__ = [
    "JaxPolicy",
    "LaneParams",
    "TrafficParams",
    "FaultParams",
    "ServingParams",
    "OverloadConfig",
    "LaneResult",
    "ClaimRecord",
    "JAX_POLICIES",
    "jax_policy_names",
    "build_policy",
    "rss_hash32",
    "reorder_metrics",
    "lane_grid",
    "run_lanes",
    "run_lanes_fused",
]

_MAWI_SIZES = np.array([40, 64, 120, 576, 1420, 1500], dtype=np.float32)
_MAWI_WEIGHTS = np.array([0.28, 0.12, 0.08, 0.10, 0.12, 0.30])
_MAWI_WEIGHTS = _MAWI_WEIGHTS / _MAWI_WEIGHTS.sum()


# ----------------------------------------------------------------------
# Parameter pytrees: one leaf value per lane (vmap axis 0)
# ----------------------------------------------------------------------
class LaneParams(NamedTuple):
    """Per-lane policy knobs (each field is a scalar or a [lanes] array)."""

    batch: jnp.ndarray  # claim-size cap (corec/scaleout/locked)
    min_batch: jnp.ndarray  # adaptive-batch lower clamp
    max_batch: jnp.ndarray  # adaptive-batch upper clamp
    claim_overhead: jnp.ndarray  # per-batch claim cost (DD scan + CAS)
    deschedule_prob: jnp.ndarray  # per-batch Bernoulli stall probability
    deschedule_mean: jnp.ndarray  # exponential stall length


class TrafficParams(NamedTuple):
    """Per-lane workload knobs (forwarder cost model + arrival process)."""

    rate: jnp.ndarray  # packets per unit time
    pkt_size: jnp.ndarray  # bytes (udp workload)
    burstiness: jnp.ndarray  # lognormal sigma of mawi gaps
    base_service: jnp.ndarray  # per-packet CPU cost
    per_byte: jnp.ndarray  # per-byte cache-touch cost
    service_jitter: jnp.ndarray  # lognormal sigma of service times
    mean_service: jnp.ndarray  # mean for the M/D/LN/HT service kinds
    diurnal_amp: jnp.ndarray  # diurnal rate modulation depth in [0, 0.95]
    diurnal_period: jnp.ndarray  # diurnal cycle length (sim time units)
    session_alpha: jnp.ndarray  # Pareto tail index of the HT service kind


def default_lane_params(**kw) -> dict:
    d = dict(
        batch=32,
        min_batch=1,
        max_batch=32,
        claim_overhead=0.05,
        deschedule_prob=0.0,
        deschedule_mean=30.0,
    )
    d.update(kw)
    return d


def default_traffic_params(**kw) -> dict:
    d = dict(
        rate=40.0,
        pkt_size=64.0,
        burstiness=0.9,
        base_service=0.07,
        per_byte=1e-5,
        service_jitter=0.25,
        mean_service=1.0,
        diurnal_amp=0.6,
        diurnal_period=50.0,
        session_alpha=1.8,
    )
    d.update(kw)
    return d


class FaultParams(NamedTuple):
    """Per-lane fault injection knobs (the jax view of ``FaultSpec``).

    One crash and one straggler per lane: ``crash_worker`` dies at
    simulated time ``crash_t`` (``+inf`` = never, the exact-identity
    default), ``straggler_worker`` serves every packet ``straggler``
    times slower.  ``lease`` is the reclamation deadline offset: a claim
    stranded by a mid-claim crash re-opens to live workers at
    ``t_claim + lease`` (``+inf`` = no lease — the stranded span is
    never re-served and the lane reports ``undelivered > 0``; policies
    with ``leases=False``, i.e. ``locked``, always behave as ``+inf``).
    """

    crash_t: jnp.ndarray  # fp32 crash/stall time (+inf = no fault)
    crash_worker: jnp.ndarray  # fp32 worker index that dies
    straggler: jnp.ndarray  # fp32 service slowdown factor (1.0 = none)
    straggler_worker: jnp.ndarray  # fp32 worker index that runs slow
    lease: jnp.ndarray  # fp32 reclamation deadline offset (+inf = off)


def default_fault_params(**kw) -> dict:
    d = dict(
        crash_t=jnp.inf,
        crash_worker=0,
        straggler=1.0,
        straggler_worker=0,
        lease=jnp.inf,
    )
    d.update(kw)
    return d


class ServingParams(NamedTuple):
    """Per-lane serving-scenario knobs (open-loop SLO sweeps).

    Like :class:`FaultParams`, every field is an *exact IEEE identity*
    at its ``+inf`` default: admission never sheds
    (``max(backlog - inf, 0) == 0``), no worker is autoscale-gated
    (``w >= inf`` is false for every worker index), the generation
    horizon masks nothing (``arr <= inf``), and the SLO comparison only
    feeds the attainment metric — so default-knob serving lanes stay
    bit-identical to the classic engine.

    ``admit_limit``
        backlog cap: a claiming worker first sheds up to ``max_batch``
        requests over the cap from its queue head (dequeue-side drop;
        must be >= 1 when finite).
    ``base_workers`` / ``scale_backlog``
        autoscaled pool: worker ``w >= base_workers`` joins only once
        its wake queue's unclaimed backlog reaches
        ``(w - base_workers + 1) * scale_backlog`` (clamped >= 1).
        ``base_workers=+inf`` = the full static pool;
        ``scale_backlog=+inf`` with finite ``base_workers`` = a fixed
        pool of exactly ``base_workers`` workers.
    ``horizon``
        open-loop generation cutoff: arrivals after it never happen
        (``offered`` counts the ones that do; the lane drains when
        ``items + shed == offered``).
    ``slo_target``
        per-user sojourn target for the SLO-attainment metric.
    """

    admit_limit: jnp.ndarray  # fp32 backlog cap (+inf = admit everything)
    base_workers: jnp.ndarray  # fp32 always-on worker count (+inf = all)
    scale_backlog: jnp.ndarray  # fp32 backlog per extra worker (+inf = off)
    horizon: jnp.ndarray  # fp32 arrival-generation cutoff (+inf = open)
    slo_target: jnp.ndarray  # fp32 sojourn target (+inf = any delivery)
    drop_rate: jnp.ndarray  # fp32 response-loss probability (0.0 = off)


def default_serving_params(**kw) -> dict:
    d = dict(
        admit_limit=jnp.inf,
        base_workers=jnp.inf,
        scale_backlog=jnp.inf,
        horizon=jnp.inf,
        slo_target=jnp.inf,
        drop_rate=0.0,
    )
    d.update(kw)
    return d


class OverloadConfig(NamedTuple):
    """Python-STATIC client/overload knobs for one serving segment.

    Unlike :class:`ServingParams` these are compile-time scalars (like
    ``sack`` / ``send_burst`` on the TCP plane): retry copies change
    array shapes and the breaker / latency-gate branches compile only
    when armed, so control-free lanes stay IEEE-bit-identical to the
    pre-overload engine.  Every knob is an exact identity at its
    default.

    ``timeout``
        client deadline per attempt: a response later than
        ``arrival + timeout`` counts ``expired`` instead of delivered.
    ``retries`` / ``backoff`` / ``jitter``
        client retry policy: attempt ``j`` (1-based) re-submits after
        a further ``timeout + (backoff + jitter * u_j) * 2**(j-1)``
        where ``u_j`` is the counter-hash draw on (lane seed, request,
        j) — ``backoff=jitter=0`` is the naive fixed-interval retry
        storm.  Retries model a no-cancellation worst case: the server
        serves every copy it admits, timely or not.
    ``hedge``
        speculative duplicate submitted ``hedge`` after the original
        (0 = off).
    ``breaker_age``
        circuit breaker (brownout): a claiming worker whose queue head
        has been waiting longer than this sheds the whole claim (up to
        ``max_batch``) instead of serving work that would expire
        anyway.
    ``scale_latency``
        latency-reactive autoscale: workers above ``base_workers``
        wake while the lane's *measured* in-graph p99 sojourn estimate
        exceeds this, replacing the ``scale_backlog`` queue-length
        gate.
    """

    timeout: float = math.inf
    retries: int = 0
    backoff: float = 0.0
    jitter: float = 0.0
    hedge: float = 0.0
    breaker_age: float = math.inf
    scale_latency: float = math.inf

    @property
    def cpr(self) -> int:
        """Copies per request (original + retries + optional hedge)."""
        return 1 + self.retries + (1 if self.hedge > 0 else 0)

    @property
    def extended(self) -> bool:
        """Whether request-level (copy-expanded) accounting is armed."""
        return self.cpr > 1 or math.isfinite(self.timeout)


_OV_OFF = OverloadConfig()

#: seed salt separating response-loss draws from retry-jitter draws
_DROP_SALT = 0xA5A5A5A5


def _pop_overload(sp: dict) -> OverloadConfig:
    """Pop the static overload knobs out of a serving_params dict.

    Mirrors the ``sack`` / ``send_burst`` pattern: these knobs must be
    python scalars (static), not lane arrays, and are validated here so
    a swept array fails loudly instead of retracing per value.
    """
    kw = {}
    if "retries" in sp:
        r = sp.pop("retries")
        if not isinstance(r, int) or isinstance(r, bool) or r < 0:
            raise ValueError("serving_params['retries'] must be an int >= 0 (static)")
        kw["retries"] = r
    for name, low in (
        ("timeout", 0.0),
        ("backoff", 0.0),
        ("jitter", 0.0),
        ("hedge", 0.0),
        ("breaker_age", 0.0),
        ("scale_latency", 0.0),
    ):
        if name in sp:
            v = sp.pop(name)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(
                    f"serving_params[{name!r}] must be a scalar float (static)"
                )
            v = float(v)
            if not v >= low or (v == 0.0 and name in ("timeout", "breaker_age")):
                raise ValueError(f"serving_params[{name!r}] must be > 0")
            kw[name] = v
    return OverloadConfig(**kw)


class LaneResult(NamedTuple):
    """Per-lane outputs of :func:`run_lanes` (each field is [lanes]).

    The last two fields count what the engine ran, not what it
    simulated: ``active_steps`` the scan steps taken before the lane's
    done predicate held, ``scan_steps`` the steps its segment's scan
    ran (chunks whose body executed, times ``chunk``; every step under
    ``engine="reference"``; the shard's own with ``shards > 1``).
    """

    p50: jnp.ndarray
    p99: jnp.ndarray
    mean: jnp.ndarray
    reorder_pct: jnp.ndarray  # RFC 4737 Type-P-Reordered ratio * 100
    max_distance: jnp.ndarray  # RFC 4737 max reordering distance
    throughput: jnp.ndarray  # packets per unit time over the busy span
    batches: jnp.ndarray  # claims issued
    items: jnp.ndarray  # packets claimed (== n_packets when lossless)
    deschedules: jnp.ndarray
    claimed_popcount: jnp.ndarray  # set bits in the packed claim bitmap
    claimed_prefix: jnp.ndarray  # contiguous done prefix of that bitmap
    claimed_words: jnp.ndarray  # [lanes, n_words] that bitmap, uint32
    sojourn: jnp.ndarray  # [lanes, n] per-packet latency, or [lanes, 0]
    # -- degraded-mode outputs (all zero / -inf-free on fault-free lanes)
    reclaimed: jnp.ndarray  # items re-opened to live workers by a lease
    duplicates: jnp.ndarray  # crashed-claim prefix re-served at-least-once
    undelivered: jnp.ndarray  # items never delivered (wedged lanes only)
    drain_t: jnp.ndarray  # last *finite* completion time (recovery edge)
    # -- serving-mode outputs (offered == n, shed == 0 off serving mode)
    offered: jnp.ndarray  # REQUESTS arriving inside the generation horizon
    shed: jnp.ndarray  # attempt copies dropped by admission / breaker
    slo_attained: jnp.ndarray  # fraction of offered meeting slo_target
    # -- overload-plane outputs (identities off serving / control mode:
    #    attempts == offered copies, delivered == goodput == items,
    #    expired == dup_served == 0).  Accounting invariants:
    #    claimed_popcount == delivered + expired + shed and
    #    delivered == goodput + dup_served.
    attempts: jnp.ndarray  # attempt copies offered (requests x retry fan-out)
    delivered: jnp.ndarray  # served copies answered in time and not lost
    expired: jnp.ndarray  # served copies past their deadline or lost
    goodput: jnp.ndarray  # unique requests with >= 1 timely response
    dup_served: jnp.ndarray  # timely responses beyond the first per request
    # -- scan counters (int32; what the engine ran, not what it simulated)
    active_steps: jnp.ndarray  # steps taken before the lane's done predicate held
    scan_steps: jnp.ndarray  # steps its segment's scan ran (the shard's, sharded)


# ----------------------------------------------------------------------
# JaxPolicy: pure-function analogues of RxPolicy's two decisions
# ----------------------------------------------------------------------
class JaxPolicy(NamedTuple):
    """A scheduling discipline as pure functions over arrays.

    ``select_queue(flows, n_workers) -> int32[n]`` is the NIC-side
    steering decision (vectorized over all packets up front);
    ``next_batch(backlog, params, n_workers) -> int32`` is the
    driver-side claim-size decision from the instantaneous backlog.
    ``shared`` means every worker drains queue 0 (single-queue
    disciplines); ``uses_lock`` serializes claims on a lock horizon
    (the Metronome-class baseline); ``steals`` lets a worker whose own
    queue is empty at claim time take the batch from the queue with the
    largest instantaneous backlog instead (hybrid work stealing);
    ``leases`` marks claims reclaimable after a crash (mirrors
    ``RxPolicy.supports_leases`` — False only for the blocking
    ``locked``, whose stranded spans wedge forever).
    """

    name: str
    shared: bool
    uses_lock: bool
    select_queue: object
    next_batch: object
    steals: bool = False
    leases: bool = True


def _fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer on uint32 — the plane's RSS hash stand-in."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def rss_hash32(key, n_queues: int):
    """Host-side mirror of the plane's steering hash (numpy, vectorized).

    The DES/threaded planes hash with 64-bit murmur mixing
    (``baseline.rss_hash``); jax's default x32 mode has no uint64, so
    the jax plane uses the murmur3 32-bit finalizer instead.  Parity
    tests feed these values to the DES plane as ``queue_hint`` so both
    planes steer identically.
    """
    h = np.asarray(key, dtype=np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h % np.uint32(n_queues)


def hash_u01(seed, a, b):
    """jnp mirror of :func:`repro.core.faults.hash_u01` (same bits).

    Counter-based uniform draw in [0, 1) keyed on ``(seed, a, b)`` —
    the impairment RNG shared across planes.  The unit scale is exact
    (rounding ``h`` to fp32 then scaling by a power of two equals
    rounding ``h * 2**-32`` to fp32), so ``hash_u01(...) < rate``
    agrees bit-for-bit with the DES mirror when the DES side compares
    through ``np.float32``.  Strict ``<`` makes ``rate == 0.0`` an
    exact never-fires identity.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    a = jnp.asarray(a).astype(jnp.uint32)
    b = jnp.asarray(b).astype(jnp.uint32)
    h = _fmix32(seed ^ (a * jnp.uint32(0x9E3779B1)))
    h = _fmix32(h ^ (b * jnp.uint32(0x85EBCA77)))
    return h.astype(jnp.float32) * jnp.float32(2.0**-32)


def queue_heads(q_arr, qptr):
    """Arrival time of each queue's next unclaimed item (+inf if none).

    ``q_arr`` rows are sorted arrival logs padded with +inf; ``qptr`` is
    the per-queue claim pointer.  Shared by the forwarder and TCP lane
    engines so both planes wake workers off the same head definition.
    """
    w = q_arr.shape[0]
    pad = q_arr.shape[1] - 1
    return q_arr[jnp.arange(w), jnp.minimum(qptr, pad)]


def row_arrived(row, t):
    """Entries <= ``t`` in one sorted row: ``searchsorted(side="right")``.

    One compare against ``t`` and a sum, so the row is read once.  The
    default ``method="scan"`` is a binary search of ceil(log2(n + 1))
    levels, and under ``vmap`` each level's one-element gather reads
    the whole batched row: 11 passes over a 2001-long row for one
    integer.  The integers are the search's, whose sort order puts NaN
    above +inf: a NaN ``t`` counts the whole row, and any other ``t``
    counts by IEEE ``<=`` (ties, +inf padding and signed zeros alike).
    """
    below = jnp.sum(row <= t, dtype=jnp.int32)
    return jnp.where(jnp.isnan(t), jnp.int32(row.shape[-1]), below)


def rows_arrived(q_arr, t0):
    """Arrivals <= ``t0`` in every sorted (+inf padded) queue row.

    :func:`row_arrived` per row: one pass over the ``[W, n+1]`` rows.
    Both lane engines count every queue's arrivals with it.
    """
    return jax.vmap(row_arrived, in_axes=(0, None))(q_arr, t0)


def steal_choice(q_arr, qptr, own, t0):
    """Hybrid victim selection at claim time ``t0``.

    Returns ``(q, backlog_q)``: the chosen queue — the worker's own when
    it has arrivals at ``t0``, else the argmax of instantaneous backlogs
    (the DES plane's ``max(len(queue))`` at dispatch time) — plus the
    per-queue backlog vector it was chosen from.  One source of truth
    for both lane engines (:mod:`jaxplane` and :mod:`tcpjax`): the
    DES-parity guarantees of both test suites pin this exact
    formulation.
    """
    backlog_q = rows_arrived(q_arr, t0) - qptr
    q = jnp.where(backlog_q[own] > 0, own, jnp.argmax(backlog_q))
    return q, backlog_q


def _select_shared(flows, n_workers):
    return jnp.zeros_like(flows, dtype=jnp.int32)


def _select_rss(flows, n_workers):
    h = _fmix32(flows.astype(jnp.uint32))
    return (h % jnp.uint32(n_workers)).astype(jnp.int32)


def _next_batch_cap(backlog, params, n_workers):
    return jnp.minimum(params.batch.astype(jnp.int32), backlog)


def _next_batch_adaptive(backlog, params, n_workers):
    share = (backlog + n_workers - 1) // n_workers
    return jnp.clip(
        share,
        params.min_batch.astype(jnp.int32),
        params.max_batch.astype(jnp.int32),
    )


# Built-in vectorized analogues.  Keep in sync with the jax_factory
# entries registered in repro.core.policy (pinned by
# tests/test_jaxplane.py::test_registry_and_jaxplane_catalogs_agree).
JAX_POLICIES = {
    "corec": JaxPolicy("corec", True, False, _select_shared, _next_batch_cap),
    "scaleout": JaxPolicy("scaleout", False, False, _select_rss, _next_batch_cap),
    "locked": JaxPolicy(
        "locked", True, True, _select_shared, _next_batch_cap, leases=False
    ),
    "hybrid": JaxPolicy(
        "hybrid", False, False, _select_rss, _next_batch_cap, steals=True
    ),
    "adaptive-batch": JaxPolicy(
        "adaptive-batch", True, False, _select_shared, _next_batch_adaptive
    ),
}


def jax_policy_names() -> list:
    return sorted(JAX_POLICIES)


def build_policy(name: str) -> JaxPolicy:
    """Resolve a policy name to its built-in vectorized analogue.

    Only the module table is consulted here (the registry's lazy
    ``jax_factory`` entries call this, so it must not call back into
    the registry); :func:`run_lanes` / :func:`run_lanes_fused` resolve
    through :func:`repro.core.policy.make_jax_policy` instead, which
    also sees runtime-registered plugin policies.
    """
    try:
        return JAX_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"policy {name!r} has no jax-plane analogue; "
            f"vectorized: {jax_policy_names()}"
        ) from None


def _resolve_policy(policy) -> JaxPolicy:
    if isinstance(policy, JaxPolicy):
        return policy
    from .policy import make_jax_policy

    return make_jax_policy(policy)


# ----------------------------------------------------------------------
# Traffic generation (in-graph, per lane)
# ----------------------------------------------------------------------
def _gen_traffic(
    key, tp: TrafficParams, workload: str, service: str, n: int, n_flows: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    kg, kf, ks, kv = jax.random.split(key, 4)
    if workload == "udp":
        gaps = jax.random.exponential(kg, (n,)) / tp.rate
        sizes = jnp.full((n,), tp.pkt_size, dtype=jnp.float32)
        flows = jax.random.randint(kf, (n,), 0, n_flows)
    elif workload == "mawi":
        sigma = tp.burstiness
        mu = jnp.log(1.0 / tp.rate) - sigma**2 / 2
        gaps = jnp.exp(jax.random.normal(kg, (n,)) * sigma + mu)
        sizes = jax.random.choice(
            ks, jnp.asarray(_MAWI_SIZES), (n,), p=jnp.asarray(_MAWI_WEIGHTS)
        )
        zipf = 1.0 / np.arange(1, n_flows + 1) ** 1.1
        zipf = jnp.asarray(zipf / zipf.sum())
        flows = jax.random.choice(kf, n_flows, (n,), p=zipf)
    elif workload == "diurnal":
        # Nonhomogeneous Poisson, lambda(t) = rate * (1 + amp sin(wt)):
        # time-rescaling — draw a unit-rate process, invert the
        # cumulative intensity Lambda(t) = rate*(t + amp/w*(1 - cos wt))
        # by vectorized Newton (lambda >= rate*(1 - amp) > 0 bounds the
        # derivative away from 0, so a dozen damped steps converge).
        s = jnp.cumsum(jax.random.exponential(kg, (n,)))
        amp = jnp.clip(tp.diurnal_amp, 0.0, 0.95)
        w = 2.0 * jnp.pi / tp.diurnal_period
        lam_min = tp.rate * (1.0 - amp)
        t = s / tp.rate
        for _ in range(12):
            big = tp.rate * (t + amp / w * (1.0 - jnp.cos(w * t)))
            lam = tp.rate * (1.0 + amp * jnp.sin(w * t))
            t = jnp.maximum(t - (big - s) / jnp.maximum(lam, lam_min), 0.0)
        gaps = None
        arr = jax.lax.cummax(t)  # Newton residue must not break sortedness
        sizes = jnp.full((n,), tp.pkt_size, dtype=jnp.float32)
        flows = jax.random.randint(kf, (n,), 0, n_flows)
    else:
        raise ValueError(f"unknown workload {workload!r}")
    if gaps is not None:
        arr = jnp.cumsum(gaps)
    if service == "fwd":  # the forwarder's per-size lognormal cost model
        mean = tp.base_service + tp.per_byte * sizes
        sj = tp.service_jitter
        svc = jnp.exp(jax.random.normal(kv, (n,)) * sj + jnp.log(mean) - sj**2 / 2)
    elif service == "M":
        svc = jax.random.exponential(kv, (n,)) * tp.mean_service
    elif service == "D":
        svc = jnp.full((n,), tp.mean_service, dtype=jnp.float32)
    elif service == "LN":
        sigma = 0.8
        mu = jnp.log(tp.mean_service) - sigma**2 / 2
        svc = jnp.exp(jax.random.normal(kv, (n,)) * sigma + mu)
    elif service == "HT":
        # Heavy-tailed session sizes: Pareto with tail index alpha > 1,
        # scaled so the (truncated at u >= 1e-4, i.e. ~p99.99) mean is
        # mean_service — inverse-CDF u^(-1/alpha) on a clipped uniform.
        alpha = tp.session_alpha
        u = jnp.maximum(jax.random.uniform(kv, (n,)), 1e-4)
        svc = tp.mean_service * (alpha - 1.0) / alpha * u ** (-1.0 / alpha)
    else:
        raise ValueError(f"unknown service kind {service!r}")
    return arr.astype(jnp.float32), svc.astype(jnp.float32), flows


def reorder_metrics(done_times: jnp.ndarray):
    """RFC 4737 NextExp metrics, in-graph, from completion times.

    Packet i's sequence number is its generation index (arrivals are
    generated in seqno order), so the completion order is
    ``argsort(done_times)`` and a packet is Type-P-Reordered iff its
    seqno is below the running max of seqnos completed before it.
    Returns ``(reordered_ratio, max_distance)`` — the packet-flavour
    reordering distance of RFC 4737 section 4.4 (displacement of a
    reordered packet past its in-order slot), matching
    :func:`repro.core.reorder.measure_reordering` on the same stream.
    """
    n = done_times.shape[0]
    order = jnp.argsort(done_times)  # completion order -> seqnos
    comp_seq = order.astype(jnp.int32)
    cummax = jax.lax.cummax(comp_seq)
    reordered = comp_seq < cummax  # NextExp: below the running max
    pos_of = jnp.argsort(order).astype(jnp.int32)  # seqno -> position
    disp = pos_of - jnp.arange(n, dtype=jnp.int32)
    dist = jnp.where((disp > 0) & reordered[pos_of], disp, 0)
    return jnp.mean(reordered.astype(jnp.float32)), jnp.max(dist)


# ----------------------------------------------------------------------
# The claim-compacted step: O(workers) state, one ClaimRecord per step
# ----------------------------------------------------------------------
class _LaneState(NamedTuple):
    """Scan carry per lane — everything else lives in the claim records."""

    qptr: jnp.ndarray  # [W] int32 per-queue claim pointer
    free_t: jnp.ndarray  # [W] fp32 per-worker free time
    lock_t: jnp.ndarray  # fp32 lock horizon (``locked`` only)
    batches: jnp.ndarray  # int32 claims issued
    items: jnp.ndarray  # int32 packets claimed (delivered, not stranded)
    deschs: jnp.ndarray  # int32 deschedule stalls taken
    # -- fault plane (all inert on fault-free lanes) -------------------
    resume_t: jnp.ndarray  # [W] fp32 lease expiry gating a stranded span
    resume_until: jnp.ndarray  # [W] int32 rank bound of the gated span
    reclaimed: jnp.ndarray  # int32 items re-opened by a lease
    dups: jnp.ndarray  # int32 crashed-prefix items re-served (at-least-once)
    halted: jnp.ndarray  # bool no claimable work remains (drained OR wedged)
    shed: jnp.ndarray  # int32 requests dropped by admission (serving mode)
    lat_est: jnp.ndarray  # fp32 in-graph p99 sojourn estimate (overload mode)


class ClaimRecord(NamedTuple):
    """One batch claim: queue, start rank, size, post-overhead time.

    Emitted per scan step by the compacted engine; masked steps carry
    ``k == shed == 0`` and the dump queue ``W``.  Everything per-packet
    — completion times, the packed claim bitmap — reconstructs from
    these after the scan.  ``k`` is the *delivered* size: a claim
    truncated by its worker's crash records only the pre-crash prefix,
    so the reconstruction never assigns completion times to packets the
    dead worker stranded.  ``shed`` (serving mode, else 0) is the
    admission-dropped span [ptr, ptr + shed): claimed — a real driver's
    drop still sets the descriptor-done bit — but never served, so
    service starts at rank ``ptr + shed``.
    """

    q: jnp.ndarray  # int32 claimed queue (W == dump)
    ptr: jnp.ndarray  # int32 first claimed rank in that queue
    k: jnp.ndarray  # int32 delivered claim size (0 == masked step)
    t1: jnp.ndarray  # fp32 claim time + overhead (+ stall)
    slow: jnp.ndarray  # fp32 straggler service multiplier (1.0 = none)
    shed: jnp.ndarray  # int32 admission-dropped span before the claim


def _init_state(lanes: int, n_workers: int) -> _LaneState:
    z = jnp.zeros((lanes,), jnp.int32)
    return _LaneState(
        qptr=jnp.zeros((lanes, n_workers), jnp.int32),
        free_t=jnp.zeros((lanes, n_workers), jnp.float32),
        lock_t=jnp.zeros((lanes,), jnp.float32),
        batches=z,
        items=z,
        deschs=z,
        resume_t=jnp.zeros((lanes, n_workers), jnp.float32),
        resume_until=jnp.zeros((lanes, n_workers), jnp.int32),
        reclaimed=z,
        dups=z,
        halted=jnp.zeros((lanes,), bool),
        shed=z,
        lat_est=jnp.zeros((lanes,), jnp.float32),
    )


def _claim_step(
    pol: JaxPolicy,
    mb: int,
    serving: bool,
    ov: OverloadConfig,
    params,
    sparams,
    q_arr,
    cumsvc,
    flt,
    st,
    u,
    stall,
):
    """One batch claim on one lane; returns the new state + its record.

    ``q_arr`` [W, n+1] sorted arrival rows (+inf padded), ``cumsvc``
    [W, n] per-queue prefix sums of service time in rank order.  The
    worker's busy span is the difference of two ``cumsvc`` gathers —
    no per-packet window is touched inside the step.

    ``flt = (crash_w, slow_w, lease)`` is the lane's fault view:
    ``crash_w`` [W] per-worker crash times (+inf = immortal), ``slow_w``
    [W] straggler service multipliers, ``lease`` the reclamation offset.
    Every fault expression is an exact identity at the defaults
    (+inf / 1.0): ``where`` masks stay false and service spans multiply
    by 1.0, so fault-free lanes remain bit-identical to the pre-fault
    engine (pinned by tests/test_compaction.py).

    ``serving`` (static) arms the :class:`ServingParams` knobs in
    ``sparams`` — the autoscale wake gate and shed-at-claim admission —
    both exact identities at the +inf defaults, on the same convention.
    ``ov`` (static, :class:`OverloadConfig`) additionally compiles in
    the circuit breaker (``breaker_age``) and the latency-reactive
    autoscale gate (``scale_latency``); at the defaults neither branch
    exists in the graph, so control-free lanes stay bit-identical.
    The serving branches sit under the name scope ``serve``.
    """
    w_count, n = cumsvc.shape
    crash_w, slow_w, lease = flt
    heads_raw = queue_heads(q_arr, st.qptr)
    # Lease gate: a span stranded by a mid-claim crash re-opens only at
    # resume_t (the claim time + lease); until qptr passes the stranded
    # bound the queue's head is pushed out to the lease expiry.
    gated = st.qptr < st.resume_until
    heads = jnp.where(gated, jnp.maximum(heads_raw, st.resume_t), heads_raw)
    if pol.steals:
        # work conserving: a worker wakes for the earliest unclaimed
        # arrival in ANY queue (it can steal), not just its own
        arr_next = jnp.broadcast_to(jnp.min(heads), (w_count,))
    elif pol.shared:
        arr_next = jnp.broadcast_to(heads[0], (w_count,))
    else:
        # scaleout failover: worker v wakes for its own queue's head, or
        # for a CRASHED peer's head (never before that peer's death) —
        # the lease-style adoption of a dead worker's pinned backlog.
        # With crash_w = +inf every cross landing is +inf: identity.
        eye = jnp.eye(w_count, dtype=bool)
        avail = jnp.maximum(heads[None, :], jnp.where(eye, -jnp.inf, crash_w[None, :]))
        arr_next = jnp.min(avail, axis=1)
    t_cand = jnp.maximum(st.free_t, arr_next)
    if pol.uses_lock:
        t_cand = jnp.maximum(t_cand, st.lock_t)
    if serving:
        with jax.named_scope("serve"):
            # Autoscale wake gate: worker w >= base_workers may not claim
            # before the ((w - base + 1) * scale_backlog)-th unclaimed
            # arrival of its wake queue exists — "add a worker per
            # scale_backlog of standing backlog", stated as a wake time so
            # the gate dissolves exactly as the claim pointer advances.
            # base_workers = +inf makes ``scaled`` all-false and the gate
            # a max with -inf: the identity.
            widx_f = jnp.arange(w_count, dtype=jnp.float32)
            scaled = widx_f >= sparams.base_workers
            thr_raw = (widx_f - sparams.base_workers + 1.0) * jnp.maximum(
                sparams.scale_backlog, 1.0
            )
            thr_i = jnp.where(scaled, jnp.clip(thr_raw, 1.0, 2.0**30), 1.0).astype(
                jnp.int32
            )
            if pol.shared:
                qsel = jnp.zeros((w_count,), jnp.int32)
            else:
                qsel = jnp.arange(w_count, dtype=jnp.int32)
            gate_idx = jnp.clip(st.qptr[qsel] + thr_i - 1, 0, n)
            t_scale = jnp.where(scaled, q_arr[qsel, gate_idx], -jnp.inf)
            if math.isfinite(ov.scale_latency):
                # latency-reactive autoscale: scaled workers wake on the
                # MEASURED p99 sojourn estimate crossing scale_latency, not
                # on queue length.  The estimate lives in the carry, so the
                # gate re-evaluates every step: workers park again once the
                # estimate decays below the threshold (hysteresis comes
                # from the asymmetric quantile update below).
                hot = st.lat_est > ov.scale_latency
                t_scale = jnp.where(
                    scaled, jnp.where(hot, -jnp.inf, jnp.inf), -jnp.inf
                )
            t_cand = jnp.maximum(t_cand, t_scale)
    # dead-worker mask: a worker whose next feasible claim would start
    # at/after its crash time never claims again (crash-between-claims)
    t_cand = jnp.where(t_cand >= crash_w, jnp.inf, t_cand)
    w = jnp.argmin(t_cand).astype(jnp.int32)
    t0 = t_cand[w]
    active = jnp.isfinite(t0)
    if pol.steals:
        # inline gated steal: identical to steal_choice() when no span
        # is lease-gated, but a helper never steals a stranded span
        # before its lease expires
        backlog_q = rows_arrived(q_arr, t0) - st.qptr
        bgate = gated & (st.resume_t > t0)
        backlog_q = jnp.where(bgate, 0, backlog_q)
        q = jnp.where(backlog_q[w] > 0, w, jnp.argmax(backlog_q)).astype(jnp.int32)
        backlog = backlog_q[q]
    elif pol.shared:
        q = jnp.int32(0)
        backlog = row_arrived(q_arr[0], t0) - st.qptr[0]
    else:
        # own queue when it is claimable at t0, else the first claimable
        # dead peer's queue (the failover wake-up above guarantees one)
        backlog_q = rows_arrived(q_arr, t0) - st.qptr
        gate_t = jnp.where(gated, st.resume_t, -jnp.inf)
        can = (jnp.arange(w_count) == w) | (crash_w <= t0)
        has = can & (backlog_q > 0) & (t0 >= gate_t)
        q = jnp.where(has[w], w, jnp.argmax(has)).astype(jnp.int32)
        backlog = backlog_q[q]
    if serving:
        with jax.named_scope("serve"):
            # Shed-at-claim admission: before serving, the claiming worker
            # drops up to max_batch over-limit requests from the queue head
            # (a real driver's dequeue-side drop still sets the done bit,
            # so shed items stay in the claim bitmap).  admit_limit = +inf
            # makes excess exactly 0.0: the identity.
            excess = jnp.maximum(
                backlog.astype(jnp.float32) - sparams.admit_limit, 0.0
            )
            shed = jnp.where(
                active, jnp.minimum(excess, float(mb)).astype(jnp.int32), 0
            )
            if math.isfinite(ov.breaker_age):
                # circuit breaker (brownout): when the queue head has aged
                # past breaker_age the whole claim is shed instead of
                # served — bounded-staleness service: work that would
                # expire anyway is dropped cheaply at the head, up to
                # max_batch per claim, keeping the shed span within the
                # claim-record window.
                head_age = t0 - q_arr[q, st.qptr[q]]
                tripped = active & (backlog > 0) & (head_age > ov.breaker_age)
                shed = jnp.where(tripped, jnp.minimum(backlog, mb), shed)
            else:
                tripped = jnp.zeros((), bool)
            backlog = backlog - shed
    else:
        shed = jnp.zeros((), jnp.int32)
        tripped = jnp.zeros((), bool)
    k = pol.next_batch(backlog, params, w_count)
    k = jnp.clip(k, jnp.minimum(backlog, 1), jnp.minimum(backlog, mb))
    k = jnp.where(active & ~tripped, k, 0).astype(jnp.int32)
    desch = active & (u < params.deschedule_prob)
    stall_t = jnp.where(desch, stall * params.deschedule_mean, 0.0)
    t1 = t0 + params.claim_overhead + stall_t
    ptr = st.qptr[q]
    ptr_s = ptr + shed  # first *served* rank (== ptr off serving mode)
    base = jnp.where(ptr_s > 0, cumsvc[q, jnp.maximum(ptr_s - 1, 0)], 0.0)
    # Straggler inflation + crash truncation: worker w serves at slow x
    # real time; it delivers the longest prefix of its claim that
    # finishes strictly before its crash time c.
    slow = slow_w[w]
    c = crash_w[w]
    svc_budget = base + (c - t1) / slow
    k_eff = row_arrived(cumsvc[q], svc_budget) - ptr_s
    k_eff = jnp.where(active, jnp.clip(k_eff, 0, k), 0).astype(jnp.int32)
    crashed = active & (k_eff < k)
    last = cumsvc[q, jnp.clip(ptr_s + k_eff - 1, 0, n - 1)]
    t_end = t1 + jnp.where(k_eff > 0, (last - base) * slow, 0.0)
    free_t_w = jnp.where(crashed, jnp.inf, jnp.where(active, t_end, st.free_t[w]))
    free_t = st.free_t.at[w].set(free_t_w)
    if pol.uses_lock:
        # lock held through claim + stall; service runs outside it.  A
        # holder that dies inside the window [t0, t1] dies INSIDE the
        # critical section: the horizon goes to +inf and every peer
        # wedges — the paper's blocking pathology under real failure.
        lock_dead = active & (c <= t1)
        lock_t = jnp.where(active, jnp.where(lock_dead, jnp.inf, t1), st.lock_t)
    else:
        lock_t = st.lock_t
    # A truncated claim strands [ptr + k_eff, ptr + k): gate the span
    # until the lease expires (t0 + lease; +inf lease = wedged forever).
    lease_v = lease if pol.leases else jnp.float32(jnp.inf)
    resume_t = jnp.where(
        crashed, st.resume_t.at[q].set(t0 + lease_v), st.resume_t
    )
    resume_until = jnp.where(
        crashed, st.resume_until.at[q].set(ptr_s + k), st.resume_until
    )
    will_reclaim = crashed & jnp.isfinite(lease_v)
    if serving and math.isfinite(ov.scale_latency):
        with jax.named_scope("serve"):
            # Robbins-Monro p99 tracker fed from claim completions: the
            # sample is the batch's max sojourn (its first served rank has
            # the earliest arrival).  est += lr * (0.99 - I[s <= est])
            # converges to the 0.99-quantile; the asymmetry (big up-steps,
            # small down-steps) doubles as scale-down hysteresis.
            samp_ok = active & (k_eff > 0)
            samp = t_end - q_arr[q, ptr_s]
            lr = jnp.float32(0.25 * ov.scale_latency)
            step = lr * (jnp.float32(0.99) - (samp <= st.lat_est).astype(jnp.float32))
            lat_est = jnp.where(
                samp_ok, jnp.maximum(st.lat_est + step, 0.0), st.lat_est
            )
    else:
        lat_est = st.lat_est
    has = (k_eff + shed) > 0 if serving else k_eff > 0
    st2 = _LaneState(
        qptr=st.qptr.at[q].add(shed + k_eff),
        free_t=free_t,
        lock_t=lock_t,
        batches=st.batches + active.astype(jnp.int32),
        items=st.items + k_eff,
        deschs=st.deschs + desch.astype(jnp.int32),
        resume_t=resume_t,
        resume_until=resume_until,
        reclaimed=st.reclaimed + jnp.where(will_reclaim, k - k_eff, 0),
        dups=st.dups + jnp.where(will_reclaim, k_eff, 0),
        halted=st.halted | ~active,
        shed=st.shed + shed,
        lat_est=lat_est,
    )
    rec = ClaimRecord(
        q=jnp.where(has, q, w_count),
        ptr=jnp.where(has, ptr, 0),
        k=k_eff,
        t1=t1,
        slow=slow,
        shed=jnp.broadcast_to(shed, k_eff.shape).astype(jnp.int32),
    )
    return st2, rec


def _scatter_claims(rec: ClaimRecord, qid, rank, cumsvc):
    """Per-packet completion times from one lane's claim records.

    The batched counterpart of the reference engine's per-claim window
    writes: scatter each claim's index at its (queue, start-rank) slot,
    forward-fill along ranks with ``cummax`` (claim indices increase
    with rank within a queue), then every packet's completion is
    ``t1[claim] + (cumsvc[rank] - cumsvc[claim_start - 1])`` — one
    gather chain over the whole lane instead of one scatter per claim.
    """
    w_count, n = cumsvc.shape
    s_total = rec.k.shape[0]
    s_idx = jnp.arange(s_total, dtype=jnp.int32)
    # masked steps (and skipped-chunk zero records) go to the dump row
    live = (rec.k + rec.shed) > 0
    qe = jnp.where(live, rec.q, w_count)
    pe = jnp.where(live, rec.ptr, 0)
    start = jnp.full((w_count + 1, n + 1), -1, jnp.int32)
    start = start.at[qe, pe].set(jnp.where(live, s_idx, -1))
    cid = jax.lax.cummax(start[:w_count], axis=1)  # forward fill
    cid_p = cid[qid, rank]  # [n] claim id covering each packet (-1: none)
    safe = jnp.maximum(cid_p, 0)
    t1_p = rec.t1[safe]
    ptr_p = rec.ptr[safe] + rec.shed[safe]  # first *served* rank
    k_p = rec.k[safe]
    slow_p = rec.slow[safe]
    base_p = jnp.where(ptr_p > 0, cumsvc[qid, jnp.maximum(ptr_p - 1, 0)], 0.0)
    in_claim = (cid_p >= 0) & (rank < ptr_p + k_p)
    served = in_claim & (rank >= ptr_p)  # shed span: claimed, not served
    done = jnp.where(
        served, t1_p + (cumsvc[qid, rank] - base_p) * slow_p, jnp.inf
    )
    return done, in_claim


def _lane_setup(
    pol: JaxPolicy,
    workload: str,
    service: str,
    n_orig: int,
    n_slots: int,
    n_flows: int,
    n_workers: int,
    n_draws: int,
    serving: bool,
    ov: OverloadConfig,
    params: LaneParams,
    traffic: TrafficParams,
    fparams: FaultParams,
    sparams: ServingParams,
    seed,
):
    """Pre-draw one lane's traffic and build its per-queue views.

    ``n_orig`` is the generated request count (identical draws to the
    pre-overload engine); ``n_slots >= n_orig`` is the shared attempt
    capacity of the fused call.  With retry/hedge knobs armed each
    request expands into ``ov.cpr`` attempt copies (original, retries
    at counter-hash-jittered backoff offsets, optional hedge), globally
    re-sorted by arrival; surplus capacity pads with never-arriving
    +inf slots so every fused segment shares one shape.
    """
    key = jax.random.PRNGKey(seed)
    kt, kd = jax.random.split(key)
    lseed = jnp.asarray(seed, jnp.uint32)
    arr, svc, flows = _gen_traffic(kt, traffic, workload, service, n_orig, n_flows)
    if serving:
        # Generation horizon: arrivals after it never happen.  They keep
        # their rank slots as +inf pad (arrivals are monotone, so the
        # masked set is a per-queue rank suffix and rows stay sorted);
        # ``offered`` is the lane's true open-loop load.
        arr = jnp.where(arr <= sparams.horizon, arr, jnp.inf)
    arr0 = arr
    if n_slots == n_orig:
        parent = jnp.arange(n_orig, dtype=jnp.int32)
        att = jnp.zeros(n_orig, dtype=jnp.int32)
    else:
        # attempt expansion: rows [cpr, n_orig] of (arrival, attempt)
        # per request.  Attempt j re-fires a further timeout +
        # (backoff + jitter * u_j) * 2**(j-1) after attempt j-1; the
        # hedge copy fires a flat ``hedge`` after the original.  A
        # client models fire-and-forget (no cancellation): copies
        # happen whether or not an earlier attempt succeeded — the
        # retry-amplification worst case.
        pidx = jnp.arange(n_orig, dtype=jnp.int32)
        rows, att_ids = [arr], [0]
        acc = jnp.zeros(n_orig, jnp.float32)
        for j in range(1, ov.retries + 1):
            u_j = hash_u01(lseed, pidx, jnp.int32(j))
            acc = acc + jnp.float32(ov.timeout) + (
                jnp.float32(ov.backoff) + jnp.float32(ov.jitter) * u_j
            ) * jnp.float32(2.0 ** (j - 1))
            rows.append(arr + acc)
            att_ids.append(j)
        if ov.hedge > 0:
            rows.append(arr + jnp.float32(ov.hedge))
            att_ids.append(ov.retries + 1)
        arr_e = jnp.concatenate(rows)
        arr_e = jnp.where(jnp.isfinite(jnp.tile(arr0, len(rows))), arr_e, jnp.inf)
        if serving:
            arr_e = jnp.where(arr_e <= sparams.horizon, arr_e, jnp.inf)
        parent = jnp.tile(pidx, len(rows))
        att = jnp.repeat(jnp.asarray(att_ids, jnp.int32), n_orig)
        pad = n_slots - arr_e.shape[0]
        if pad:
            arr_e = jnp.concatenate([arr_e, jnp.full(pad, jnp.inf, jnp.float32)])
            parent = jnp.concatenate([parent, jnp.zeros(pad, jnp.int32)])
            att = jnp.concatenate([att, jnp.full(pad, ov.retries + 2, jnp.int32)])
        order = jnp.argsort(arr_e)  # stable: rank construction needs
        arr = arr_e[order]  # globally arrival-sorted slots
        parent = parent[order]
        att = att[order]
        svc = jnp.where(jnp.isfinite(arr), svc[parent], 0.0)
        flows = flows[parent]
    qid = pol.select_queue(flows, n_workers)  # [n] in [0, W)
    # rank of each packet within its queue (arrival order is global order)
    n = arr.shape[0]
    rank = jnp.zeros(n, dtype=jnp.int32)
    for w in range(n_workers):
        m = qid == w
        rank = jnp.where(m, jnp.cumsum(m.astype(jnp.int32)) - 1, rank)
    # q_arr[w, r] = arrival time of queue w's r-th packet (pad: +inf)
    q_arr = jnp.full((n_workers, n + 1), jnp.inf, dtype=jnp.float32)
    q_arr = q_arr.at[qid, rank].set(arr)
    # cumsvc[w, r] = prefix sum of service times in rank order
    svc_qr = jnp.zeros((n_workers, n), dtype=jnp.float32).at[qid, rank].set(svc)
    cumsvc = jnp.cumsum(svc_qr, axis=1)
    ku, ke = jax.random.split(kd)
    u_desch = jax.random.uniform(ku, (n_draws,))
    stalls = jax.random.exponential(ke, (n_draws,)).astype(jnp.float32)
    # per-worker fault views along the worker axis (identity defaults:
    # +inf crash time, 1.0 service multiplier)
    widx = jnp.arange(n_workers, dtype=jnp.float32)
    crash_w = jnp.where(widx == fparams.crash_worker, fparams.crash_t, jnp.inf)
    slow_w = jnp.where(widx == fparams.straggler_worker, fparams.straggler, 1.0)
    su = dict(
        arr=arr,
        qid=qid,
        rank=rank,
        q_arr=q_arr,
        cumsvc=cumsvc,
        u=u_desch,
        stalls=stalls,
        crash_w=crash_w.astype(jnp.float32),
        slow_w=slow_w.astype(jnp.float32),
        lease=jnp.float32(fparams.lease),
    )
    if serving:
        # offered counts attempt COPIES (the drain predicate's unit);
        # offered_req counts the requests behind them
        su["offered"] = jnp.sum(jnp.isfinite(arr)).astype(jnp.int32)
        su["offered_req"] = jnp.sum(jnp.isfinite(arr0)).astype(jnp.int32)
        su["parent"] = parent
        su["att"] = att
        su["arr0"] = arr0
        su["lseed"] = lseed
    return su


def _reference_lane(
    pol: JaxPolicy,
    mb: int,
    serving: bool,
    ov: OverloadConfig,
    lane_done,
    params,
    sparams,
    su,
):
    """The pre-compaction per-claim scan: windows written inside the step.

    Shares :func:`_claim_step` with the compacted engine and applies
    each record's completion window to a (queue, rank) grid immediately
    — the formulation ``tests/test_compaction.py`` pins the compacted
    reconstruction against, bit for bit.  In serving mode a separate
    claimed grid is maintained (shed spans are claimed but never get a
    finite completion, so ``isfinite(done)`` no longer implies claimed).
    Every step runs; ``lane_done(st, su)`` only counts the steps taken
    before it held (``active_steps``).
    """
    q_arr, cumsvc = su["q_arr"], su["cumsvc"]
    qid, rank = su["qid"], su["rank"]
    flt = (su["crash_w"], su["slow_w"], su["lease"])
    w_count, n = cumsvc.shape
    cs_pad = jnp.concatenate(
        [cumsvc, jnp.broadcast_to(cumsvc[:, -1:], (w_count, mb))], axis=1
    )
    cs_pad = jnp.concatenate([cs_pad, jnp.zeros((1, n + mb), jnp.float32)])
    done_qr0 = jnp.full((w_count + 1, n + mb), jnp.inf, dtype=jnp.float32)
    clm_qr0 = jnp.zeros((w_count + 1, n + mb), dtype=bool)
    lane_st0 = jax.tree_util.tree_map(lambda x: x[0], _init_state(1, w_count))

    def step(carry, xs):
        st, done_qr, clm_qr, active = carry
        u, stall = xs
        active = active + (~lane_done(st, su)).astype(jnp.int32)
        st2, rec = _claim_step(
            pol, mb, serving, ov, params, sparams, q_arr, cumsvc, flt, st, u, stall
        )
        ptr_s = rec.ptr + rec.shed  # first *served* rank
        row = jax.lax.dynamic_slice(done_qr, (rec.q, ptr_s), (1, mb))[0]
        cs = jax.lax.dynamic_slice(cs_pad, (rec.q, ptr_s), (1, mb))[0]
        base = jnp.where(ptr_s > 0, cs_pad[rec.q, jnp.maximum(ptr_s - 1, 0)], 0.0)
        comp = rec.t1 + (cs - base) * rec.slow
        neww = jnp.where(jnp.arange(mb) < rec.k, comp, row)
        done_qr = jax.lax.dynamic_update_slice(done_qr, neww[None], (rec.q, ptr_s))
        if serving:
            # shed window [ptr, ptr+shed) and served window [ptr_s,
            # ptr_s+k) — both <= mb wide, together the full claim
            idx = jnp.arange(mb)
            crow = jax.lax.dynamic_slice(clm_qr, (rec.q, rec.ptr), (1, mb))[0]
            crow = crow | (idx < rec.shed)
            clm_qr = jax.lax.dynamic_update_slice(
                clm_qr, crow[None], (rec.q, rec.ptr)
            )
            srow = jax.lax.dynamic_slice(clm_qr, (rec.q, ptr_s), (1, mb))[0]
            srow = srow | (idx < rec.k)
            clm_qr = jax.lax.dynamic_update_slice(
                clm_qr, srow[None], (rec.q, ptr_s)
            )
        return (st2, done_qr, clm_qr, active), None

    carry0 = (lane_st0, done_qr0, clm_qr0, jnp.int32(0))
    (st, done_qr, clm_qr, active), _ = jax.lax.scan(
        step, carry0, (su["u"], su["stalls"])
    )
    done = done_qr[qid, rank]
    claimed = clm_qr[qid, rank] if serving else jnp.isfinite(done)
    return st, done, claimed, active


# ----------------------------------------------------------------------
# Chunked scan with a real done short-circuit (scan outside the vmap)
# ----------------------------------------------------------------------
def _chunked_scan(body, carry0, xs, lane_done, chunk: int):
    """``lax.scan`` over chunks of ``chunk`` steps with early exit.

    ``body`` advances ALL lanes one step (it is vmapped internally by
    the caller); ``lane_done(carry) -> bool[lanes]`` is each lane's
    done predicate.  Each chunk is guarded by ``lax.cond``: once every
    lane reports done, remaining chunks skip both the state update and
    the per-step outputs (zero records — masked downstream).  The
    leading xs axis must be a multiple of ``chunk``.

    Returns ``(carry, ys, active_steps, scan_steps)``, the last two
    int32 per lane: the steps taken before the lane's own predicate
    held, and the steps the scan ran (chunks whose ``run`` branch
    executed, times ``chunk``).  The output shapes and the ``run``
    branch scan the same counting body, so JAX traces the step once.
    A chunk that runs is one ``while`` op under the name scope
    ``chunk.<chunk>``.
    """
    s_total = jax.tree_util.tree_leaves(xs)[0].shape[0]
    n_chunks = s_total // chunk
    xs_c = jax.tree_util.tree_map(
        lambda x: x.reshape((n_chunks, chunk) + x.shape[1:]), xs
    )
    x0 = jax.tree_util.tree_map(lambda x: x[0], xs_c)

    def counted(carry, x):
        c, active = carry
        active = active + (~lane_done(c)).astype(jnp.int32)
        c, y = body(c, x)
        return (c, active), y

    active0 = jnp.zeros(jax.eval_shape(lane_done, carry0).shape, jnp.int32)
    ys_aval = jax.eval_shape(
        lambda c, x: jax.lax.scan(counted, c, x)[1], (carry0, active0), x0
    )

    def chunk_body(carry, xc):
        def run(carry):
            c, active, chunks = carry
            with jax.named_scope(f"chunk.{chunk}"):
                (c, active), ys = jax.lax.scan(counted, (c, active), xc)
            return (c, active, chunks + 1), ys

        def skip(carry):
            zeros = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), ys_aval
            )
            return carry, zeros

        return jax.lax.cond(jnp.all(lane_done(carry[0])), skip, run, carry)

    (carry, active, chunks), ys = jax.lax.scan(
        chunk_body, (carry0, active0, jnp.int32(0)), xs_c
    )
    ys = jax.tree_util.tree_map(lambda y: y.reshape((s_total,) + y.shape[2:]), ys)
    return carry, ys, active, jnp.full(active.shape, chunks * chunk, jnp.int32)


# ----------------------------------------------------------------------
# The fused core: every policy segment in one scan, one jitted call
# ----------------------------------------------------------------------
def _masked_percentile(svals, n_del, qv: float):
    """np.percentile (linear interpolation) over the first ``n_del``
    entries of each pre-sorted row (+inf tail = undelivered pad)."""
    nd = jnp.maximum(n_del, 1)
    pos = qv / 100.0 * (nd - 1).astype(jnp.float32)
    lo = jnp.floor(pos).astype(jnp.int32)
    frac = pos - lo.astype(jnp.float32)
    vlo = jnp.take_along_axis(svals, lo[:, None], axis=-1)[:, 0]
    vhi = jnp.take_along_axis(
        svals, jnp.minimum(lo + 1, nd - 1)[:, None], axis=-1
    )[:, 0]
    # frac == 0 exact ranks skip the lerp (vhi may be the +inf pad on
    # empty lanes; 0 * inf would poison the result with NaN)
    return jnp.where(frac > 0, vlo + frac * (vhi - vlo), vlo)


def _sweep_core(
    blocks,
    pols,
    workload: str,
    service: str,
    n_packets: int,
    n_workers: int,
    max_batch: int,
    n_flows: int,
    s_pad: int,
    chunk: int,
    engine: str,
    serving: bool,
    ovs,
    max_cpr: int,
    return_times: bool,
):
    """Simulate every lane of every policy segment; returns per-segment
    dicts of lane-axis arrays (safe to wrap in ``shard_map``).

    ``ovs`` is one static :class:`OverloadConfig` per segment;
    ``max_cpr`` is the largest copies-per-request across them — every
    segment shares the ``n_packets * max_cpr`` attempt-slot shape
    (segments with fewer copies pad with never-arriving slots).
    """
    n, mb = n_packets, max_batch
    n_slots = n_packets * max_cpr
    setups, states = [], []
    for pol, ov, (params, traffic, fparams, sparams, seeds) in zip(
        pols, ovs, blocks
    ):
        with jax.named_scope(f"seg.{pol.name}"), jax.named_scope("lane_setup"):
            setup = jax.vmap(
                functools.partial(
                    _lane_setup,
                    pol,
                    workload,
                    service,
                    n,
                    n_slots,
                    n_flows,
                    n_workers,
                    s_pad,
                    serving,
                    ov,
                )
            )(params, traffic, fparams, sparams, seeds)
        setups.append(setup)
        states.append(_init_state(seeds.shape[0], n_workers))

    def lane_done(st, su):
        # a lane is finished when it drained OR wedged (no claimable
        # work remains: dead lock holder, unleased stranded span) —
        # wedged lanes must not burn the budget.  Serving lanes drain
        # at their own offered load (shed requests count: they
        # consumed a claim slot).
        if serving:
            return st.halted | (st.items + st.shed >= su["offered"])
        return st.halted | (st.items >= n)

    if engine == "reference":
        finals = []
        for pol, ov, (params, _, _, sparams, _), su in zip(
            pols, ovs, blocks, setups
        ):
            lane = functools.partial(_reference_lane, pol, mb, serving, ov, lane_done)
            with jax.named_scope(f"seg.{pol.name}"), jax.named_scope("scan"):
                st, done, claimed, active = jax.vmap(lane)(params, sparams, su)
            finals.append((st, done, claimed, active, jnp.full_like(active, s_pad)))
    elif engine == "compacted":
        # one specialized chunked scan PER policy segment, all inside
        # the one jitted call: each policy's lanes stop paying for the
        # claim budget at their own drain point, and each segment's
        # step compiles without the untaken policies' branches (a
        # per-lane flag dispatch was measured slower than static
        # segmentation here — the step is compute-bound, not
        # dispatch-bound, at sweep lane counts)
        finals = []
        for pol, ov, (params, _, _, sparams, _), su, st0 in zip(
            pols, ovs, blocks, setups, states
        ):
            step = functools.partial(_claim_step, pol, mb, serving, ov)

            def body(carry, x, step=step, params=params, sparams=sparams, su=su):
                u, stall = x
                flt = (su["crash_w"], su["slow_w"], su["lease"])
                return jax.vmap(step)(
                    params, sparams, su["q_arr"], su["cumsvc"], flt, carry, u, stall
                )

            with jax.named_scope(f"seg.{pol.name}"):
                xs = (su["u"].T, su["stalls"].T)
                with jax.named_scope("scan"):
                    st, rec, active, scanned = _chunked_scan(
                        body, st0, xs, functools.partial(lane_done, su=su), chunk
                    )
                with jax.named_scope("claims"):
                    rec_l = ClaimRecord(*(x.T for x in rec))  # [S, Lp] -> [Lp, S]
                    done, claimed = jax.vmap(_scatter_claims)(
                        rec_l, su["qid"], su["rank"], su["cumsvc"]
                    )
            finals.append((st, done, claimed, active, scanned))
    else:
        raise ValueError(f"unknown engine {engine!r}")

    outs = []
    for pol, ov, (_, _, _, sparams, _), su, fin in zip(
        pols, ovs, blocks, setups, finals
    ):
        st, done, claimed, active, scanned = fin
        with jax.named_scope(f"seg.{pol.name}"), jax.named_scope("post_scan"):
            out = _segment_outputs(
                n, serving, ov, sparams, su, st, done, claimed, return_times
            )
        outs.append(dict(out, active_steps=active, scan_steps=scanned))
    return tuple(outs)


def _segment_outputs(n, serving, ov, sparams, su, st, done, claimed, return_times):
    """One segment's per-lane outputs from its final lane state and the
    reconstructed completions: latency, reorder and accounting."""
    words = kernel_ops.pack_bits_u32(claimed)
    ratio, max_dist = jax.vmap(reorder_metrics)(done)
    if serving:
        # Open-loop metrics: only delivered requests have latencies
        # (shed and stranded carry done=+inf, horizon-masked slots
        # carry arr=done=+inf), so every aggregate masks on
        # delivery and percentiles interpolate over the delivered
        # prefix of the sorted row — matching np.percentile on the
        # delivered subset exactly (pinned by tests).  A served
        # attempt only counts delivered when its response survives
        # drop_rate (counter-hash on request + attempt; all-false
        # at the 0.0 identity) AND, with a timeout armed, returns
        # within timeout of ITS OWN submission.
        served = jnp.isfinite(done)
        lost = (
            hash_u01(
                su["lseed"][:, None] ^ jnp.uint32(_DROP_SALT),
                su["parent"],
                su["att"],
            )
            < sparams.drop_rate[:, None]
        )
        delivered = served & ~lost
        attempts = su["offered"].astype(jnp.int32)
        if ov.extended:
            # request-level accounting: a request is good when ANY
            # of its attempt copies answers within its deadline;
            # later timely copies are duplicate work (dup_served)
            delivered = delivered & (done <= su["arr"] + jnp.float32(ov.timeout))
            lanes_i = jnp.arange(done.shape[0])[:, None]
            first_ok = (
                jnp.full((done.shape[0], n), jnp.inf)
                .at[lanes_i, su["parent"]]
                .min(jnp.where(delivered, done, jnp.inf))
            )
            deliv_req = jnp.isfinite(first_ok)
            sojourn = jnp.where(deliv_req, first_ok - su["arr0"], jnp.inf)
            arr_lat = su["arr0"]
            offered = su["offered_req"].astype(jnp.int32)
        else:
            sojourn = jnp.where(delivered, done - su["arr"], jnp.inf)
            deliv_req = delivered
            arr_lat = su["arr"]
            offered = su["offered"].astype(jnp.int32)
        n_del = jnp.sum(deliv_req, axis=-1).astype(jnp.int32)
        svals = jnp.sort(sojourn, axis=-1)
        p50 = _masked_percentile(svals, n_del, 50.0)
        p99 = _masked_percentile(svals, n_del, 99.0)
        mean = jnp.sum(
            jnp.where(deliv_req, sojourn, 0.0), axis=-1
        ) / jnp.maximum(n_del, 1)
        ok = deliv_req & (sojourn <= sparams.slo_target[:, None])
        slo_att = jnp.sum(ok, axis=-1) / jnp.maximum(offered, 1)
        drain_t = jnp.max(
            jnp.where(jnp.isfinite(done), done, -jnp.inf), axis=-1
        )
        t_first = jnp.min(arr_lat, axis=-1)
        span = jnp.maximum(drain_t - t_first, 1e-9)
        throughput = st.items / span
        undelivered = (attempts - st.items - st.shed).astype(jnp.int32)
        n_deliv_cp = jnp.sum(delivered, axis=-1).astype(jnp.int32)
        expired = st.items - n_deliv_cp
        goodput = n_del
        dup_served = n_deliv_cp - goodput
    else:
        sojourn = done - su["arr"]
        pct = jnp.percentile(sojourn, jnp.asarray([50.0, 99.0]), axis=-1)
        p50, p99 = pct[0], pct[1]
        mean = jnp.mean(sojourn, axis=-1)
        offered = jnp.full(st.items.shape, n, dtype=jnp.int32)
        # closed loop: every request is offered and none shed, so
        # attainment degenerates to the delivered fraction
        slo_att = st.items.astype(jnp.float32) / n
        # Undelivered items (wedged lanes) carry done=+inf; the
        # recovery edge is the last *finite* completion, and the
        # busy span uses it so faulted lanes still report a finite
        # throughput denominator.
        drain_t = jnp.max(jnp.where(jnp.isfinite(done), done, -jnp.inf), axis=-1)
        span = drain_t - jnp.min(su["arr"], axis=-1)
        throughput = n / span
        undelivered = (n - st.items).astype(jnp.int32)
        # no client plane off serving mode: every claimed item is a
        # delivered original
        attempts = offered
        expired = jnp.zeros_like(st.items)
        goodput = st.items
        dup_served = jnp.zeros_like(st.items)
    return dict(
        p50=p50,
        p99=p99,
        mean=mean,
        reorder_pct=100.0 * ratio,
        max_distance=max_dist,
        throughput=throughput,
        batches=st.batches,
        items=st.items,
        deschedules=st.deschs,
        claimed_popcount=jnp.sum(
            jax.lax.population_count(words), axis=-1
        ).astype(jnp.int32),
        words=words,
        reclaimed=st.reclaimed,
        duplicates=st.dups,
        undelivered=undelivered,
        drain_t=drain_t,
        offered=offered,
        shed=st.shed,
        slo_attained=slo_att.astype(jnp.float32),
        attempts=attempts,
        delivered=goodput + dup_served,
        expired=expired,
        goodput=goodput,
        dup_served=dup_served,
        sojourn=sojourn if return_times else sojourn[:, :0],
    )


def _attach_prefix(outs, n_bits: int, limit, *, impl: str, interpret: bool):
    """Add each segment's ``"prefix"``: the contiguous done prefix of its
    packed claim words (``"words"``), from ONE multi-ring kernel launch
    over every segment.  Runs inside the lane ``shard_map`` when lanes
    are sharded, since a Mosaic kernel cannot be partitioned by XLA.
    ``limit`` caps each row (``None`` = ``n_bits``).  The launch sits
    under the name scope ``done_prefix``."""
    words = jnp.concatenate([o["words"] for o in outs], axis=0)
    if limit is None:
        limit = jnp.full((words.shape[0],), n_bits, dtype=jnp.int32)
    with jax.named_scope("done_prefix"):
        prefix = kernel_ops.done_prefix_packed(
            words, limit, n_bits=n_bits, impl=impl, interpret=interpret
        )
    res, at = [], 0
    for o in outs:
        lanes = o["words"].shape[0]
        res.append(dict(o, prefix=prefix[at : at + lanes]))
        at += lanes
    return tuple(res)


def _run_fused_impl(
    blocks,
    *,
    pols,
    workload: str,
    service: str,
    n_packets: int,
    n_workers: int,
    max_batch: int,
    n_flows: int,
    s_pad: int,
    chunk: int,
    n_shards: int,
    engine: str,
    serving: bool,
    ovs,
    max_cpr: int,
    prefix_impl: str,
    prefix_interpret: bool,
    return_times: bool,
):
    def core(blocks):
        outs = _sweep_core(
            blocks,
            pols=pols,
            workload=workload,
            service=service,
            n_packets=n_packets,
            n_workers=n_workers,
            max_batch=max_batch,
            n_flows=n_flows,
            s_pad=s_pad,
            chunk=chunk,
            engine=engine,
            serving=serving,
            ovs=ovs,
            max_cpr=max_cpr,
            return_times=return_times,
        )
        # exactly-once on the packed words (bit width = the attempt-slot
        # capacity when retry fan-out is armed)
        return _attach_prefix(
            outs,
            n_packets * max_cpr,
            None,
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
        LaneResult(
            p50=o["p50"],
            p99=o["p99"],
            mean=o["mean"],
            reorder_pct=o["reorder_pct"],
            max_distance=o["max_distance"],
            throughput=o["throughput"],
            batches=o["batches"],
            items=o["items"],
            deschedules=o["deschedules"],
            claimed_popcount=o["claimed_popcount"],
            claimed_prefix=o["prefix"],
            claimed_words=o["words"],
            sojourn=o["sojourn"],
            reclaimed=o["reclaimed"],
            duplicates=o["duplicates"],
            undelivered=o["undelivered"],
            drain_t=o["drain_t"],
            offered=o["offered"],
            shed=o["shed"],
            slo_attained=o["slo_attained"],
            attempts=o["attempts"],
            delivered=o["delivered"],
            expired=o["expired"],
            goodput=o["goodput"],
            dup_served=o["dup_served"],
            active_steps=o["active_steps"],
            scan_steps=o["scan_steps"],
        )
        for o in core(blocks)
    )


_FUSED_STATICS = (
    "pols",
    "workload",
    "service",
    "n_packets",
    "n_workers",
    "max_batch",
    "n_flows",
    "s_pad",
    "chunk",
    "n_shards",
    "engine",
    "serving",
    "ovs",
    "max_cpr",
    "prefix_impl",
    "prefix_interpret",
    "return_times",
)


@functools.lru_cache(maxsize=None)
def _fused_jit(donate: bool):
    # fp32/int32/uint32 lane-axis inputs are donated where the backend
    # supports aliasing (CPU does not; donating there only warns)
    return jax.jit(
        _run_fused_impl,
        static_argnames=_FUSED_STATICS,
        donate_argnums=(0,) if donate else (),
    )


def _pad_lanes(tree, pad: int):
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])]
        ),
        tree,
    )


def _broadcast_lanes(d: dict, fields, lanes: int, dtype=jnp.float32):
    vals = []
    for f in fields:
        v = jnp.asarray(d[f], dtype=dtype)
        if v.ndim == 0:
            v = jnp.full((lanes,), v, dtype=dtype)
        if v.shape[0] != lanes:
            raise ValueError(f"param {f!r} has {v.shape[0]} lanes, want {lanes}")
        vals.append(v)
    return vals


def _call_fused(fn, args, static: dict, timings: dict | None):
    """Call a fused jit; with a ``timings`` dict, go through the AOT
    lower/compile path and record ``compile_s``, ``run_s`` (to
    ``block_until_ready``) and ``mosaic_kernels``, the number of Pallas
    kernel calls the compiled program holds (0 where the done-prefix
    took its XLA path)."""
    if timings is None:
        return fn(*args, **static)
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    t1 = time.perf_counter()
    outs = compiled(*args)
    jax.block_until_ready(outs)
    t2 = time.perf_counter()
    timings["compile_s"] = t1 - t0
    timings["run_s"] = t2 - t1
    timings["mosaic_kernels"] = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"'
    )
    return outs


def _resolve_shards(shards) -> int:
    if shards in ("auto", None):
        return jax.local_device_count()
    return max(1, int(shards))


def _fused_lanes(
    requests,
    *,
    workload: str = "udp",
    service: str = "fwd",
    n_packets: int = 2000,
    n_workers: int = 4,
    max_batch: int = 64,
    n_flows: int = 256,
    engine: str = "compacted",
    serving: bool = False,
    claim_budget: int | None = None,
    chunk: int = 64,
    shards: int | str = 1,
    prefix_impl: str = "auto",
    prefix_interpret: bool = False,
    return_times: bool = False,
    timings: dict | None = None,
):
    """Simulate every lane of every request in ONE jitted call.

    ``requests`` is a sequence of dicts ``{"policy": name-or-JaxPolicy,
    "seeds": [...], "lane_params": {...}, "traffic_params": {...}}`` —
    one statically-bounded lane segment per request, all advanced by
    the same claim-compacted scan (policies resolve through the
    registry, so runtime-registered plugins fuse too).  Returns one
    :class:`LaneResult` per request, in order.  The supported public
    surface is :func:`repro.core.run_sweep` (a ``SweepRequest`` maps
    onto these request dicts); :func:`run_lanes` remains the
    single-segment convenience wrapper.

    ``claim_budget`` bounds claim events per lane (rounded UP to the
    next multiple of ``chunk`` — the effective scan length); the
    default ``n_packets`` is always sufficient (every active claim
    takes >= 1 packet) and the chunked ``done`` short-circuit stops
    paying for the budget once every lane drains.  A tighter budget
    trades a possible loud exactly-once failure (claimed_popcount < n)
    for shorter compiles.  ``shards`` > 1 (or ``"auto"`` = all local devices)
    partitions the lane axis across devices via ``shard_map``; each
    segment is padded to a multiple of the shard count and the padding
    is dropped from the results.  ``timings``, when a dict is passed,
    receives ``compile_s`` / ``run_s`` measured through the AOT
    lower/compile path.

    ``serving`` (or any request carrying ``serving_params``) switches
    the open-loop serving scenario on: ``n_packets`` becomes the lane's
    generation *capacity* rather than its load — the per-lane
    :class:`ServingParams` horizon decides how many of those drawn
    arrivals are offered — and results report ``offered`` / ``shed`` /
    ``slo_attained`` with delivery-masked latency aggregates.

    For a profiler trace, building the lane blocks is marked
    ``repro.prepare`` and the jitted call ``repro.dispatch``; the
    program's set-up goes to :mod:`repro.core.record`.
    """
    requests = list(requests)
    if not requests:
        raise ValueError("run_lanes_fused: empty request list")
    record.install()
    with jax.profiler.TraceAnnotation("repro.prepare"):
        args, static, orig_lanes = _fused_args(
            requests, serving, shards, chunk, n_packets, claim_budget
        )
    static.update(
        workload=workload,
        service=service,
        n_packets=n_packets,
        n_workers=n_workers,
        max_batch=max_batch,
        n_flows=n_flows,
        engine=engine,
        prefix_impl=prefix_impl,
        prefix_interpret=prefix_interpret,
        return_times=return_times,
    )
    fn = _fused_jit(jax.default_backend() != "cpu")
    with jax.profiler.TraceAnnotation("repro.dispatch"):
        outs = _call_fused(fn, args, static, timings)
    return [
        jax.tree_util.tree_map(lambda a: a[:lanes], res)
        for res, lanes in zip(outs, orig_lanes)
    ]


def _fused_args(requests, serving, shards, chunk, n_packets, claim_budget):
    """The lane blocks of the fused call, its shape statics and each
    request's lane count (before padding to the shard count)."""
    serving = serving or any(req.get("serving_params") for req in requests)
    n_shards = _resolve_shards(shards)
    chunk = max(1, int(chunk))

    pols, blocks, orig_lanes, ovs = [], [], [], []
    for req in requests:
        pol = _resolve_policy(req["policy"])
        seeds = jnp.asarray(np.asarray(req["seeds"], dtype=np.uint32))
        lanes = seeds.shape[0]
        lp = default_lane_params(**(req.get("lane_params") or {}))
        tp = default_traffic_params(**(req.get("traffic_params") or {}))
        fp = default_fault_params(**(req.get("fault_params") or {}))
        sp = default_serving_params(**(req.get("serving_params") or {}))
        # overload-control knobs are STATIC per segment (retry fan-out
        # changes shapes; the breaker / latency-gate branches compile
        # only when armed) — popped before the sweep-knob validation
        # like ``sack`` / ``send_burst`` on the TCP plane
        ov = _pop_overload(sp)
        unknown = set(lp) - set(LaneParams._fields)
        unknown |= set(tp) - set(TrafficParams._fields)
        unknown |= set(fp) - set(FaultParams._fields)
        unknown |= set(sp) - set(ServingParams._fields)
        if unknown:
            raise ValueError(f"unknown sweep knobs: {sorted(unknown)}")
        params = LaneParams(*_broadcast_lanes(lp, LaneParams._fields, lanes))
        traffic = TrafficParams(*_broadcast_lanes(tp, TrafficParams._fields, lanes))
        fparams = FaultParams(*_broadcast_lanes(fp, FaultParams._fields, lanes))
        sparams = ServingParams(*_broadcast_lanes(sp, ServingParams._fields, lanes))
        pad = (-lanes) % n_shards
        pols.append(pol)
        ovs.append(ov)
        blocks.append(_pad_lanes((params, traffic, fparams, sparams, seeds), pad))
        orig_lanes.append(lanes)

    # every fused segment shares the attempt-slot shape: requests *
    # the largest per-segment copy fan-out (1 when no retry knobs)
    max_cpr = max(ov.cpr for ov in ovs)
    n_slots = n_packets * max_cpr
    budget = n_slots if claim_budget is None else int(claim_budget)
    budget = max(1, min(budget, n_slots))
    s_pad = -(-budget // chunk) * chunk
    static = dict(
        pols=tuple(pols),
        s_pad=s_pad,
        chunk=chunk,
        n_shards=n_shards,
        serving=serving,
        ovs=tuple(ovs),
        max_cpr=max_cpr,
    )
    return (tuple(blocks),), static, orig_lanes


def run_lanes_fused(requests, **kw):
    """Deprecated alias of the fused engine entry point.

    Use :func:`repro.core.run_sweep` with a ``SweepRequest`` instead —
    this shim forwards verbatim (same results, bit for bit) and will be
    removed once downstream callers migrate.
    """
    warnings.warn(
        "run_lanes_fused is deprecated; build a repro.core.SweepRequest "
        "and call repro.core.run_sweep instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return _fused_lanes(requests, **kw)


def run_lanes(
    policy: str,
    seeds,
    lane_params: dict | None = None,
    traffic_params: dict | None = None,
    fault_params: dict | None = None,
    serving_params: dict | None = None,
    workload: str = "udp",
    service: str = "fwd",
    n_packets: int = 2000,
    n_workers: int = 4,
    max_batch: int = 64,
    n_flows: int = 256,
    prefix_impl: str = "auto",
    prefix_interpret: bool = False,
    return_times: bool = False,
    engine: str = "compacted",
    claim_budget: int | None = None,
    chunk: int = 64,
    shards: int | str = 1,
) -> LaneResult:
    """Simulate every lane of a (policy-param, seed) batch in one jit.

    ``lane_params`` / ``traffic_params`` map knob names to scalars (all
    lanes share the value) or [lanes] arrays (a sweep axis); unknown
    knobs raise.  ``seeds`` defines the lane count.  Per-batch claim
    sizes are capped by the static ``max_batch``.  A single-segment
    wrapper over :func:`run_lanes_fused` — see there for the
    ``engine`` / ``claim_budget`` / ``chunk`` / ``shards`` knobs.
    """
    return _fused_lanes(
        [
            dict(
                policy=policy,
                seeds=seeds,
                lane_params=lane_params,
                traffic_params=traffic_params,
                fault_params=fault_params,
                serving_params=serving_params,
            )
        ],
        workload=workload,
        service=service,
        n_packets=n_packets,
        n_workers=n_workers,
        max_batch=max_batch,
        n_flows=n_flows,
        engine=engine,
        claim_budget=claim_budget,
        chunk=chunk,
        shards=shards,
        prefix_impl=prefix_impl,
        prefix_interpret=prefix_interpret,
        return_times=return_times,
    )[0]


def lane_grid(axes: dict, seeds) -> Tuple[dict, list]:
    """Cartesian sweep helper: {knob: values} x seeds -> per-lane arrays.

    Returns ``(lane_arrays, points)`` where ``lane_arrays`` maps each
    knob to a [n_configs * n_seeds] array (seed-major within each
    config) ready for :func:`run_lanes`, and ``points`` lists one
    (config dict, seed) pair per lane for labelling results.
    """
    names = sorted(axes)
    grids = np.meshgrid(*[np.asarray(axes[k]) for k in names], indexing="ij")
    flat = [g.reshape(-1) for g in grids]
    n_cfg = flat[0].shape[0] if flat else 1
    seeds = np.asarray(seeds)
    lane_arrays = {k: np.repeat(v, seeds.shape[0]) for k, v in zip(names, flat)}
    seed_lanes = np.tile(seeds, n_cfg)
    points = []
    for c in range(n_cfg):
        cfg = {k: flat[i][c].item() for i, k in enumerate(names)}
        for s in seeds:
            points.append((cfg, int(s)))
    lane_arrays["__seeds__"] = seed_lanes
    return lane_arrays, points
