"""Plain references, independent of the program under test.

``forwarder_lane`` simulates one forwarder lane the straightforward
way: one claim at a time in Python, the paper's receive policies as
plain rules, completion times written per claim, statistics with numpy.
It shares no code with ``repro``.  Its inputs are the lane's raw random
variates, drawn from the lane seed with ``jax.random`` exactly as the
sweep's documented generator draws them (``forwarder_draws``); every
number made from them (arrivals, service times, queues, claims) is the
reference's own arithmetic.  ``dtype`` sets the precision of that
arithmetic: float32, the precision the configurations state, is the
reference, and bfloat16 the control.

``done_prefix`` is the packed done-prefix kernel's plain definition.
"""

from __future__ import annotations

import bisect
import math

import numpy as np

#: the packet-size mix the bursty generator draws from: the program's
#: synthetic six-size table (named after MAWI there; no trace backs it)
MIX_SIZES = np.array([40, 64, 120, 576, 1420, 1500], dtype=np.float32)
MIX_WEIGHTS = np.array([0.28, 0.12, 0.08, 0.10, 0.12, 0.30])
MIX_WEIGHTS = MIX_WEIGHTS / MIX_WEIGHTS.sum()
ZIPF_S = 1.1

#: the receive policies: (one shared queue, claims behind a lock,
#: steals from the longest backlog, adaptive claim size)
POLICIES = {
    "corec": (True, False, False, False),
    "scaleout": (False, False, False, False),
    "locked": (True, True, False, False),
    "hybrid": (False, False, True, False),
    "adaptive-batch": (True, False, False, True),
}


def fmix32(h: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer: the RSS hash that steers flows."""
    h = np.asarray(h, dtype=np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def forwarder_draws(seeds, arrival: str, n: int, n_flows: int, n_steps: int) -> dict:
    """Raw variates of each lane seed, on the host's CPU device.

    Key tree: ``PRNGKey(seed) -> (traffic, claims)``; traffic splits into
    (gaps, flows, sizes, service), claims into (stall draw, stall length).
    """
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    seeds = np.asarray(seeds, dtype=np.uint32)

    def one(seed):
        kt, kd = jax.random.split(jax.random.PRNGKey(seed))
        kg, kf, ks, kv = jax.random.split(kt, 4)
        out = {}
        if arrival == "poisson":
            out["gap"] = jax.random.exponential(kg, (n,))
            out["flow"] = jax.random.randint(kf, (n,), 0, n_flows)
        elif arrival == "bursty":
            out["gap"] = jax.random.normal(kg, (n,))
            out["size"] = jax.random.choice(
                ks, jnp.asarray(MIX_SIZES), (n,), p=jnp.asarray(MIX_WEIGHTS)
            )
            zipf = 1.0 / np.arange(1, n_flows + 1) ** ZIPF_S
            out["flow"] = jax.random.choice(
                kf, n_flows, (n,), p=jnp.asarray(zipf / zipf.sum())
            )
        else:
            raise ValueError(f"no reference for arrival {arrival!r}")
        out["svc"] = jax.random.normal(kv, (n,))
        ku, ke = jax.random.split(kd)
        out["u"] = jax.random.uniform(ku, (n_steps,))
        out["stall"] = jax.random.exponential(ke, (n_steps,))
        return out

    with jax.default_device(cpu):
        got = jax.jit(jax.vmap(one))(jax.device_put(seeds, cpu))
        return {k: np.asarray(v) for k, v in got.items()}


def _rounder(dtype):
    if np.dtype(dtype) == np.float64:
        return lambda x: x
    return lambda x: float(dtype(x))


def forwarder_lane(
    policy: str,
    knobs: dict,
    draws: dict,
    arrival: str,
    n_workers: int,
    max_batch: int,
    dtype=np.float32,
) -> dict:
    """One lane of the open-loop forwarder, simulated claim by claim.

    ``knobs`` holds the lane's values of every lane and traffic knob;
    ``draws`` one lane of :func:`forwarder_draws`.  Returns the lane's
    statistics under the program's names.
    """
    shared, locked, steals, adaptive = POLICIES[policy]
    r = _rounder(dtype)
    dt = np.dtype(dtype)

    def arr_(x):
        return np.asarray(x, dtype=np.float64).astype(dt)

    rate = arr_(knobs["rate"])
    if arrival == "poisson":
        gaps = arr_(draws["gap"]) / rate
        sizes = np.full(draws["gap"].shape, knobs["pkt_size"], dtype=dt)
    else:
        sigma = arr_(knobs["burstiness"])
        mu = np.log(arr_(1.0) / rate) - sigma * sigma / arr_(2.0)
        gaps = np.exp(arr_(draws["gap"]) * sigma + mu)
        sizes = arr_(draws["size"])
    arr = np.cumsum(gaps, dtype=dt)
    sj = arr_(knobs["service_jitter"])
    mean = arr_(knobs["base_service"]) + arr_(knobs["per_byte"]) * sizes
    svc = np.exp(arr_(draws["svc"]) * sj + np.log(mean) - sj * sj / arr_(2.0))
    n = arr.shape[0]

    if shared:
        qid = np.zeros(n, dtype=np.int64)
    else:
        qid = (fmix32(draws["flow"]) % np.uint32(n_workers)).astype(np.int64)
    members = [np.flatnonzero(qid == w) for w in range(n_workers)]
    q_arr = [arr[m].astype(np.float64).tolist() for m in members]
    cums = [np.cumsum(svc[m], dtype=dt) for m in members]

    batch = int(knobs["batch"])
    lo_b = int(knobs.get("min_batch", 1))
    hi_b = int(knobs.get("max_batch", batch))
    overhead = float(arr_(knobs["claim_overhead"]))
    p_desch = float(arr_(knobs["deschedule_prob"]))
    stall_mean = float(arr_(knobs["deschedule_mean"]))
    u = draws["u"].astype(np.float64)
    stalls = arr_(draws["stall"]).astype(np.float64)

    inf = math.inf
    qptr = [0] * n_workers
    free = [0.0] * n_workers
    lock_t = 0.0
    batches = items = deschs = 0
    done_q = [np.full(len(m), np.inf) for m in members]
    step = 0
    while True:
        heads = [q_arr[w][qptr[w]] if qptr[w] < len(q_arr[w]) else inf
                 for w in range(n_workers)]
        if steals:
            wake = [min(heads)] * n_workers
        elif shared:
            wake = [heads[0]] * n_workers
        else:
            wake = heads
        t_cand = [max(free[w], wake[w]) for w in range(n_workers)]
        if locked:
            t_cand = [max(t, lock_t) for t in t_cand]
        t0 = min(t_cand)
        if t0 == inf:
            break
        w = t_cand.index(t0)
        if shared:
            q = 0
            backlog = bisect.bisect_right(q_arr[0], t0) - qptr[0]
        else:
            bq = [bisect.bisect_right(q_arr[v], t0) - qptr[v]
                  for v in range(n_workers)]
            if bq[w] > 0:
                q = w
            elif steals:
                q = bq.index(max(bq))
            else:
                q = next(v for v in range(n_workers) if bq[v] > 0)
            backlog = bq[q]
        if adaptive:
            k = min(max(-(-backlog // n_workers), lo_b), hi_b)
        else:
            k = min(batch, backlog)
        k = min(max(k, min(backlog, 1)), min(backlog, max_batch))
        desch = u[step] < p_desch
        stall_t = r(stalls[step] * stall_mean) if desch else 0.0
        t1 = r(r(t0 + overhead) + stall_t)
        ptr = qptr[q]
        cs = cums[q]
        base = float(cs[ptr - 1]) if ptr > 0 else 0.0
        seg = cs[ptr : ptr + k].astype(np.float64)
        done_q[q][ptr : ptr + k] = [r(t1 + r(c - base)) for c in seg]
        free[w] = float(done_q[q][ptr + k - 1])
        if locked:
            lock_t = t1
        qptr[q] += k
        batches += 1
        items += k
        deschs += int(desch)
        step += 1

    done = np.empty(n)
    for w, m in enumerate(members):
        done[m] = done_q[w]
    sojourn = np.array([r(x) for x in done - arr.astype(np.float64)])
    ratio, max_dist = reorder(done)
    return dict(
        p50=float(np.percentile(sojourn, 50)),
        p99=float(np.percentile(sojourn, 99)),
        mean=float(np.mean(sojourn)),
        reorder_pct=100.0 * ratio,
        max_distance=int(max_dist),
        batches=batches,
        items=items,
        deschedules=deschs,
    )


def reorder(done: np.ndarray):
    """RFC 4737 NextExp reordering of packets completed at ``done``
    (sequence number = generation index): the reordered share and the
    largest displacement of a reordered packet past its in-order slot."""
    n = done.shape[0]
    order = np.argsort(done, kind="stable")
    reordered = order < np.maximum.accumulate(order)
    pos_of = np.argsort(order, kind="stable")
    disp = pos_of - np.arange(n)
    dist = np.where((disp > 0) & reordered[pos_of], disp, 0)
    return float(np.mean(reordered)), int(dist.max()) if n else 0


def done_prefix(words: np.ndarray, limit: np.ndarray) -> np.ndarray:
    """Contiguous set-bit run from bit 0 of each packed row (bit b of
    word j is slot 32*j + b), capped at the row's ``limit``."""
    words = np.asarray(words, dtype=np.uint32)
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    flat = bits.reshape(words.shape[0], -1).astype(bool)
    run = np.where(flat.all(axis=1), flat.shape[1], np.argmin(flat, axis=1))
    return np.minimum(run, np.asarray(limit)).astype(np.int64)


def tcp_draws(seeds, tx_budget: int, n_steps: int) -> dict:
    """Raw variates of each TCP lane seed: ``PRNGKey(seed)`` splits into
    (service, stall draw, stall length) streams."""
    import jax

    cpu = jax.devices("cpu")[0]
    seeds = np.asarray(seeds, dtype=np.uint32)

    def one(seed):
        kv, ku, ke = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {
            "svc": jax.random.normal(kv, (tx_budget,)),
            "u": jax.random.uniform(ku, (n_steps,)),
            "stall": jax.random.exponential(ke, (n_steps,)),
        }

    with jax.default_device(cpu):
        got = jax.jit(jax.vmap(one))(jax.device_put(seeds, cpu))
        return {k: np.asarray(v) for k, v in got.items()}


def tcp_lane(
    policy: str,
    knobs: dict,
    draws: dict,
    flow_packets,
    flow_start,
    n_workers: int,
    max_batch: int,
    tx_budget: int,
    n_steps: int,
    send_burst: int = 32,
    dtype=np.float32,
) -> dict:
    """One closed-loop TCP lane, event by event.

    Senders put window bursts on one serialized access link; segments
    reach the forwarder's queues after the propagation delay; workers
    claim batches by the policy's rules; each claimed segment is served
    and its ACK returns 2 x prop_delay after service.  The sender is
    NewReno: slow start and congestion avoidance, fast retransmit at an
    adaptive dup-ACK threshold that DSACK raises, window undo on a
    spurious retransmit, and a reset to the initial window with the
    hole resent after ``rto`` when nothing is left in flight.  Each
    step retires the earliest event (send burst, claim, ACK, timeout,
    in that order on ties); step ``i`` owns stall draw ``i``.
    """
    shared, locked, steals, adaptive = POLICIES[policy]
    r = _rounder(dtype)
    T = np.dtype(dtype).type
    inf = math.inf
    F, W = len(flow_packets), n_workers
    budget = max(int(knobs.get("pkt_budget", 1 << 30)), 0)
    neff = [min(int(p), budget) for p in flow_packets]
    max_pkts = int(max(flow_packets))
    link_pps = float(T(knobs["link_pps"]))
    spacing = r(1.0 / link_pps)
    prop = float(T(knobs["prop_delay"]))
    rwnd = float(T(knobs["rwnd"]))
    beta = float(T(knobs["cubic_beta"]))
    rto = float(T(knobs["rto"]))
    init_cwnd = float(T(knobs["init_cwnd"]))
    max_reo = int(knobs["max_reorder_thresh"])
    overhead = float(T(knobs["claim_overhead"]))
    p_desch = float(T(knobs["deschedule_prob"]))
    stall_mean = float(T(knobs["deschedule_mean"]))
    batch = int(knobs["batch"])
    lo_b = int(knobs.get("min_batch", 1))
    hi_b = int(knobs.get("max_batch", batch))
    sj = float(T(knobs["service_jitter"]))
    mu = r(r(math.log(float(T(knobs["service_mean"])))) - r(r(sj * sj) / 2.0))
    svc = [r(math.exp(r(r(float(x) * sj) + mu))) for x in draws["svc"]]
    u = draws["u"].astype(np.float64)
    stalls = [float(T(x)) for x in draws["stall"]]

    # steering: a flow's segments go to queue 0 or to its RSS queue
    if shared:
        qid_flow = [0] * F
        worker_queue = [0] * W
    else:
        qid_flow = [int(h) for h in fmix32(np.arange(F)) % np.uint32(W)]
        worker_queue = list(range(W))

    cwnd = [init_cwnd] * F
    ssthresh = [inf] * F
    next_seq = [0] * F
    high_ack = [-1] * F
    dup = [0] * F
    infl = [0] * F
    retx = [0] * F
    spur = [0] * F
    reo = [int(knobs["init_reorder_thresh"])] * F
    cwnd_before = [0.0] * F
    last_retx = [-1] * F
    pend = [-1] * F
    done = [False] * F
    t_done = [0.0] * F
    t_ready = [float(T(t)) for t in flow_start]
    received = [np.zeros(max_pkts, dtype=bool) for _ in range(F)]
    link_free = 0.0
    txf, txs = [], []
    tack = [inf] * tx_budget
    q_arr = [[] for _ in range(W)]
    q_idx = [[] for _ in range(W)]
    qptr = [0] * W
    freet = [0.0] * W
    lock_t = 0.0
    claimed = np.zeros(tx_budget, dtype=bool)
    batches = items = deschs = 0
    t_now = 0.0

    for step in range(n_steps):
        wnd = [int(min(cwnd[f], rwnd)) for f in range(F)]
        nsend = len(txf)
        can = [
            not done[f] and infl[f] < wnd[f]
            and (pend[f] >= 0 or next_seq[f] < neff[f]) and nsend < tx_budget
            for f in range(F)
        ]
        tsf = [t_ready[f] if can[f] else inf for f in range(F)]
        f_sel = tsf.index(min(tsf))
        t_send = max(tsf[f_sel], link_free) if tsf[f_sel] < inf else inf
        heads = [
            q_arr[w][qptr[w]] if qptr[w] < len(q_arr[w]) else inf for w in range(W)
        ]
        if steals:
            wake = [min(heads)] * W
        else:
            wake = [heads[worker_queue[w]] for w in range(W)]
        t_cand = [max(freet[w], wake[w]) for w in range(W)]
        if locked:
            t_cand = [max(t, lock_t) for t in t_cand]
        t_claim = min(t_cand)
        w_sel = t_cand.index(t_claim)
        t_ack = min(tack)
        live = any(not done[f] and neff[f] > 0 for f in range(F))
        idle = t_send == inf and t_claim == inf and t_ack == inf
        if not live and idle:
            break
        t_rto = t_now if live and idle else inf
        times = [t_send, t_claim, t_ack, t_rto]
        t_ev = min(times)
        ev = times.index(t_ev)
        t_now = t_ev

        if ev == 0:  # a window burst onto the link
            fd = f_sel
            space = max(wnd[fd] - infl[fd], 0)
            nh = 1 if pend[fd] >= 0 else 0
            fresh = max(neff[fd] - next_seq[fd], 0)
            n_take = min(space, nh + fresh, tx_budget - nsend, send_burst)
            n_rtx = min(nh, n_take)
            seqs = [
                pend[fd] if ii < n_rtx else next_seq[fd] + ii - nh
                for ii in range(n_take)
            ]
            next_seq[fd] += n_take - n_rtx
            infl[fd] += n_take
            if n_rtx > 0:
                pend[fd] = -1
            q = qid_flow[fd]
            for ii, seq in enumerate(seqs):
                depart = r(t_send + r(spacing * (ii + 1)))
                q_arr[q].append(r(depart + prop))
                q_idx[q].append(len(txf))
                txf.append(fd)
                txs.append(seq)
            link_free = r(t_send + r(spacing * n_take))
        elif ev == 1:  # a batch claim
            t0 = t_claim
            if shared:
                q = 0
                backlog = bisect.bisect_right(q_arr[0], t0) - qptr[0]
            else:
                bq = [bisect.bisect_right(q_arr[v], t0) - qptr[v] for v in range(W)]
                own = worker_queue[w_sel]
                q = own if (not steals or bq[own] > 0) else bq.index(max(bq))
                backlog = bq[q]
            if adaptive:
                k = min(max(-(-backlog // W), lo_b), hi_b)
            else:
                k = min(batch, backlog)
            k = min(max(k, 1), min(backlog, max_batch))
            desch = u[step] < p_desch
            stall_t = r(stalls[step] * stall_mean) if desch else 0.0
            t1 = r(r(t0 + overhead) + stall_t)
            served = 0.0
            for g in q_idx[q][qptr[q] : qptr[q] + k]:
                served = r(served + svc[g])
                tack[g] = r(r(t1 + served) + r(2.0 * prop))
                claimed[g] = True
            freet[w_sel] = r(t1 + served)
            if locked:
                lock_t = t1
            qptr[q] += k
            batches += 1
            items += k
            deschs += int(desch)
        elif ev == 2:  # an ACK
            j = tack.index(t_ack)
            tack[j] = inf
            fa, sa = txf[j], txs[j]
            rec = received[fa]
            dup_seg = bool(rec[sa])
            rec[sa] = True
            pref = max_pkts if rec.all() else int(np.argmin(rec))
            ackno = pref - 1
            alive = not done[fa]
            if alive and dup_seg:
                spur[fa] += 1
                reo[fa] = min(reo[fa] + 4, max_reo)
                if cwnd_before[fa] > cwnd[fa]:
                    cwnd[fa] = cwnd_before[fa]
            adv = alive and ackno > high_ack[fa]
            done_now = False
            if adv:
                newly = float(ackno - high_ack[fa])
                infl[fa] = max(0, infl[fa] - (ackno - high_ack[fa]))
                cw = cwnd[fa]
                cwnd[fa] = r(cw + (newly if cw < ssthresh[fa] else r(newly / cw)))
                high_ack[fa] = ackno
                if ackno >= neff[fa] - 1:
                    done_now = True
                    done[fa] = True
                    t_done[fa] = t_ack
            dupinc = alive and not adv and not dup_seg
            fire = dupinc and dup[fa] + 1 >= reo[fa]
            missing = high_ack[fa] + 1
            if (fire and missing < neff[fa] and missing != last_retx[fa]
                    and pend[fa] < 0):
                pend[fa] = missing
                retx[fa] += 1
                last_retx[fa] = missing
                infl[fa] = max(0, infl[fa] - 1)
                cut = max(2.0, r(cwnd[fa] * beta))
                cwnd_before[fa] = cwnd[fa]
                ssthresh[fa] = cut
                cwnd[fa] = cut
            if adv or fire:
                dup[fa] = 0
            elif dupinc:
                dup[fa] += 1
            if alive and not done_now:
                t_ready[fa] = t_ack
        else:  # timeout: every live flow restarts from its hole
            for f in range(F):
                if done[f] or neff[f] <= 0:
                    continue
                missing = high_ack[f] + 1
                ssthresh[f] = max(2.0, r(cwnd[f] * beta))
                cwnd[f] = init_cwnd
                infl[f] = 0
                dup[f] = 0
                if missing < neff[f]:
                    if pend[f] != missing:
                        retx[f] += 1
                    pend[f] = missing
                    last_retx[f] = missing
                t_ready[f] = r(t_now + rto)

    delivered = []
    for f in range(F):
        rec = received[f]
        pref = max_pkts if rec.all() else int(np.argmin(rec))
        delivered.append(min(pref, neff[f]))
    start = [float(T(t)) for t in flow_start]
    return dict(
        fct=[r(t_done[f] - start[f]) if done[f] else inf for f in range(F)],
        done=list(done),
        retransmissions=retx,
        spurious=spur,
        delivered=delivered,
        sends=len(txf),
        batches=batches,
        items=items,
        deschedules=deschs,
        claimed_popcount=int(claimed.sum()),
    )
