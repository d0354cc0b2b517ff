"""Plain reference of one open-loop serving lane, independent of the
program under test.

``serving_lane`` simulates one lane of ``SweepRequest(scenario="serving")``
the straightforward way: one claim at a time in Python over per-queue
lists, the receive policies as plain rules (``reference.POLICIES``), the
autoscale gate and shed-at-claim admission as ``ServingParams`` documents
them, completion times written per claim, statistics with numpy.  It
imports nothing of ``repro``; the program's own event mirror
(``servingjax.simulate_serving_des``) shares the model under test and is
not used here.

Its inputs are the lane's raw random variates, drawn from the lane seed
with ``jax.random`` on the host CPU in the program's key layout
(``serving_draws``); every number made from them is the reference's own
arithmetic in ``dtype``: float32, the precision the configuration
states, is the reference, and bfloat16 the control.

One departure from the program's method, not its model: the diurnal
arrival times invert the cumulative intensity
``Lambda(t) = rate * (t + amp / w * (1 - cos(w t)))`` at the unit-rate
arrival sums by bisection in float64, then round to ``dtype``; the
program runs twelve Newton steps in float32.  The two agree within a few
float32 ulps of the arrival time (under 1e-4 at t = 500).

The model of a lane, with ``W`` workers and claim cap ``max_batch``:

* arrivals: all ``capacity`` users arrive (the generation horizon is
  +inf; the scenario's guarantees hold every lane to that).  Steering:
  every user to queue 0 (one shared queue), or to queue
  ``fmix32(flow) % W`` (per-core RSS queues).
* service: Pareto session sizes ``mean * (alpha - 1) / alpha *
  max(u, 1e-4) ** (-1 / alpha)``, served in queue order; a claim of
  ``k`` users completes each at the claim time plus overhead (plus a
  stall) plus the service of the users before it in the claim.
* worker ``w`` may claim at ``max(free[w], head)``: the head of queue 0
  (shared), of its own queue (RSS), or of any queue (stealing); behind
  the lock horizon for ``locked``.  Autoscale: a worker ``w >=
  base_workers`` may not claim before the ``(w - base_workers + 1) *
  max(scale_backlog, 1)``-th unclaimed user of its wake queue (queue 0
  shared, else its own) has arrived.  The earliest candidate claims;
  ties go to the lowest worker.
* admission: the claiming worker first sheds ``min(backlog -
  admit_limit, max_batch)`` users from the queue head when its backlog
  is over ``admit_limit``; shed users are claimed, never served.  Then
  it takes ``next_batch`` of the rest, between 1 and ``max_batch``.
* claim ``i`` of the lane owns stall draw ``i``.
"""

from __future__ import annotations

import bisect
import math

import numpy as np

from .reference import POLICIES, fmix32

#: the smallest uniform the Pareto inverse CDF takes (about p99.99)
U_FLOOR = 1e-4
#: bisection steps of the diurnal inversion, far past float64 precision
BISECT_STEPS = 80


def serving_draws(seeds, n: int, n_flows: int, n_steps: int) -> dict:
    """Raw variates of each lane seed, on the host's CPU device.

    Key tree: ``PRNGKey(seed) -> (traffic, claims)``; traffic splits into
    (gaps, flows, sizes, service), claims into (stall draw, stall length).
    The diurnal process draws unit-rate gaps; the sizes key is unused.
    """
    import jax

    cpu = jax.devices("cpu")[0]
    seeds = np.asarray(seeds, dtype=np.uint32)

    def one(seed):
        kt, kd = jax.random.split(jax.random.PRNGKey(seed))
        kg, kf, _, kv = jax.random.split(kt, 4)
        ku, ke = jax.random.split(kd)
        return {
            "gap": jax.random.exponential(kg, (n,)),
            "flow": jax.random.randint(kf, (n,), 0, n_flows),
            "svc_u": jax.random.uniform(kv, (n,)),
            "u": jax.random.uniform(ku, (n_steps,)),
            "stall": jax.random.exponential(ke, (n_steps,)),
        }

    with jax.default_device(cpu):
        got = jax.jit(jax.vmap(one))(jax.device_put(seeds, cpu))
        return {k: np.asarray(v) for k, v in got.items()}


def diurnal_arrivals(s: np.ndarray, rate: float, amp: float, period: float):
    """Times ``t`` with ``Lambda(t) = s``, in float64, by bisection.

    ``Lambda`` is increasing (its slope ``rate * (1 + amp sin wt)`` is at
    least ``rate * (1 - amp) > 0``), ``Lambda(t) >= rate * t`` and
    ``Lambda(t) <= rate * t + 2 rate amp / w``, which brackets the root.
    """
    s = np.asarray(s, dtype=np.float64)
    amp = min(max(amp, 0.0), 0.95)
    w = 2.0 * math.pi / period
    lo = np.maximum((s - 2.0 * rate * amp / w) / rate, 0.0)
    hi = np.maximum(s / rate, lo)
    for _ in range(BISECT_STEPS):
        mid = 0.5 * (lo + hi)
        over = rate * (mid + amp / w * (1.0 - np.cos(w * mid))) > s
        hi = np.where(over, mid, hi)
        lo = np.where(over, lo, mid)
    return 0.5 * (lo + hi)


def _rounder(dtype):
    return lambda x: float(np.asarray(x, dtype=np.float64).astype(dtype))


def serving_lane(
    policy: str,
    knobs: dict,
    draws: dict,
    n_workers: int,
    max_batch: int,
    dtype=np.float32,
    given: dict | None = None,
) -> dict:
    """One open-loop serving lane, simulated claim by claim.

    ``knobs`` holds the lane's value of every lane, traffic and serving
    knob; ``draws`` one lane of :func:`serving_draws`.  Returns the
    lane's statistics under the program's names.

    ``given`` is for a witness, never for ``correct``: another
    computation's float32 inputs in place of the reference's own,
    ``arr`` the lane's arrival times and ``cumsvc`` each queue's service
    prefix sums in rank order (``[W, n]`` rows), so that only the claim
    model is compared.
    """
    shared, locked, steals, adaptive = POLICIES[policy]
    r = _rounder(dtype)
    dt = np.dtype(dtype)
    inf = math.inf

    def arr_(x):
        return np.asarray(x, dtype=np.float64).astype(dt)

    # arrivals: unit-rate sums, inverted through the diurnal intensity
    s = np.cumsum(arr_(draws["gap"]), dtype=dt)
    t = diurnal_arrivals(
        s, *(float(arr_(knobs[k])) for k in ("rate", "diurnal_amp", "diurnal_period"))
    )
    arr = arr_(t) if given is None else arr_(given["arr"])
    offered = arr.shape[0]
    # Pareto session sizes
    alpha = arr_(knobs["session_alpha"])
    u = np.maximum(arr_(draws["svc_u"]), arr_(U_FLOOR))
    scale = arr_(knobs["mean_service"]) * (alpha - arr_(1.0)) / alpha
    svc = scale * np.power(u, -arr_(1.0) / alpha)

    W = n_workers
    if shared:
        qid = np.zeros(offered, dtype=np.int64)
    else:
        qid = (fmix32(draws["flow"]) % np.uint32(W)).astype(np.int64)
    members = [np.flatnonzero(qid == v) for v in range(W)]
    q_arr = [arr[m].astype(np.float64).tolist() for m in members]
    cums = [np.cumsum(svc[m], dtype=dt).astype(np.float64) for m in members]
    if given is not None:
        cums = [np.asarray(given["cumsvc"][v][: len(m)], np.float64)
                for v, m in enumerate(members)]

    batch = int(knobs["batch"])
    lo_b, hi_b = int(knobs["min_batch"]), int(knobs["max_batch"])
    overhead = float(arr_(knobs["claim_overhead"]))
    p_desch = float(arr_(knobs["deschedule_prob"]))
    stall_mean = float(arr_(knobs["deschedule_mean"]))
    admit = float(knobs["admit_limit"])
    base_w = float(knobs["base_workers"])
    per_worker = max(float(knobs["scale_backlog"]), 1.0)
    thr = [min(max((v - base_w + 1.0) * per_worker, 1.0), 2.0**30) for v in range(W)]
    gated = [v >= base_w for v in range(W)]
    u_d = np.asarray(draws["u"], dtype=np.float64)
    stalls = arr_(draws["stall"]).astype(np.float64)

    qptr = [0] * W
    free = [0.0] * W
    lock_t = 0.0
    batches = items = shed_total = deschs = 0
    done_q = [np.full(len(m), inf) for m in members]
    step = 0
    while True:
        heads = [q_arr[v][qptr[v]] if qptr[v] < len(q_arr[v]) else inf
                 for v in range(W)]
        if steals:
            wake = [min(heads)] * W
        elif shared:
            wake = [heads[0]] * W
        else:
            wake = heads
        t_cand = [max(free[v], wake[v]) for v in range(W)]
        if locked:
            t_cand = [max(x, lock_t) for x in t_cand]
        for v in range(W):
            if gated[v]:
                gq = 0 if shared else v
                idx = qptr[gq] + int(thr[v]) - 1
                gate = q_arr[gq][idx] if idx < len(q_arr[gq]) else inf
                t_cand[v] = max(t_cand[v], gate)
        t0 = min(t_cand)
        if t0 == inf:
            break
        w = t_cand.index(t0)
        if shared:
            q = 0
            backlog = bisect.bisect_right(q_arr[0], t0) - qptr[0]
        else:
            bq = [bisect.bisect_right(q_arr[v], t0) - qptr[v] for v in range(W)]
            q = w if (bq[w] > 0 or not steals) else bq.index(max(bq))
            backlog = bq[q]
        shed = int(min(max(backlog - admit, 0.0), max_batch))
        backlog -= shed
        if adaptive:
            k = min(max(-(-backlog // W), lo_b), hi_b)
        else:
            k = min(batch, backlog)
        k = min(max(k, min(backlog, 1)), min(backlog, max_batch))
        desch = u_d[step] < p_desch
        stall_t = r(stalls[step] * stall_mean) if desch else 0.0
        t1 = r(r(t0 + overhead) + stall_t)
        ptr_s = qptr[q] + shed
        cs = cums[q]
        base = cs[ptr_s - 1] if ptr_s > 0 else 0.0
        ends = [r(t1 + r(c - base)) for c in cs[ptr_s : ptr_s + k]]
        done_q[q][ptr_s : ptr_s + k] = ends
        free[w] = ends[-1] if ends else t1
        if locked:
            lock_t = t1
        qptr[q] = ptr_s + k
        batches += 1
        items += k
        shed_total += shed
        deschs += int(desch)
        step += 1

    done = np.full(offered, inf)
    for v, m in enumerate(members):
        done[m] = done_q[v]
    served = np.isfinite(done)
    sojourn = np.array([r(x) for x in done[served] - arr[served].astype(np.float64)])
    slo = float(knobs["slo_target"])
    some = sojourn.size > 0
    return dict(
        p50=float(np.percentile(sojourn, 50)) if some else inf,
        p99=float(np.percentile(sojourn, 99)) if some else inf,
        mean=float(np.mean(sojourn)) if some else 0.0,
        slo_attained=float(np.sum(sojourn <= slo)) / max(offered, 1),
        batches=batches,
        items=items,
        shed=shed_total,
        undelivered=offered - items - shed_total,
        deschedules=deschs,
    )
