"""Continuous-batching inference engine with a COREC ingestion queue.

Dataflow (the paper's Rx pipeline, serving edition):

  frontend --submit--> scheduler (COREC shared ring | RSS per-worker rings)
      --claim (CAS)--> ingestion workers: prefill the prompt, stage the
      per-request cache --> decode loop: inserts staged requests into free
      decode slots, steps ALL active slots in one batched ``decode_step``,
      retires finished sequences.

Decode slots form ``n_lanes`` rings with the paper's producer-credit
semantics (lane = a hardware Rx queue of the decode batch): each lane has
an admission cursor ``head`` and a ``tail`` that advances only over the
*contiguous* prefix of finished slots.  All lanes' releasable prefixes
are computed on-device in ONE batched ``pallas_call``
(kernels/doneprefix ``[R, n]`` variant — R TAIL-register writes from a
single kernel launch), so slot recycling cost is independent of the lane
count.  Admission order is checkpointable per lane exactly like the
NIC's credit scheme.  A straggling sequence delays only its own lane's
slot reuse, never any peer's decoding — section 3.4.4's corner case,
verified in tests/test_serving.py; extra lanes bound the blast radius of
a straggler to ``n_slots / n_lanes`` slots.  ``contiguous_release=False``
gives the free-list alternative for A/B comparison (more capacity under
stragglers, unordered admission).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ArchConfig
from ..kernels import ops
from ..models.api import build_model
from ..models.spec import abstract_params
from .request import Request, RequestResult
from .scheduler import make_scheduler

__all__ = ["EngineConfig", "InferenceEngine"]


@dataclass
class EngineConfig:
    n_slots: int = 8  # decode slots (total, across all lanes)
    max_seq: int = 64  # cache capacity per slot
    n_workers: int = 2  # ingestion (prefill) workers
    policy: str = "corec"  # 'corec' | 'rss'
    claim_batch: int = 4
    eos_token: int = 1
    contiguous_release: bool = True  # paper's TAIL rule for slot reuse
    greedy: bool = True
    n_lanes: int = 1  # decode slot rings; released in ONE batched kernel


class InferenceEngine:
    def __init__(self, cfg: ArchConfig, ecfg: EngineConfig, params=None,
                 rng: Optional[jax.Array] = None):
        self.cfg = cfg
        self.ecfg = ecfg
        self.model = build_model(cfg)
        self.params = params if params is not None else self.model.init(
            rng if rng is not None else jax.random.PRNGKey(0)
        )
        self.sched = make_scheduler(ecfg.policy, ecfg.n_workers)
        B, S = ecfg.n_slots, ecfg.max_seq

        self.cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            abstract_params(self.model.cache_specs(B, S)),
        )
        self._decode = jax.jit(lambda p, c, t: self.model.decode_step(p, c, t))
        self._prefill = jax.jit(lambda p, b: self.model.prefill(p, b, max_seq=S))

        # slot ring bookkeeping (host side): R lanes of B/R slots each;
        # global slot id = lane * lane_slots + offset
        if B % ecfg.n_lanes:
            raise ValueError("n_slots must be divisible by n_lanes")
        self.n_lanes = ecfg.n_lanes
        self.lane_slots = B // ecfg.n_lanes
        self.slot_req: List[Optional[RequestResult]] = [None] * B
        self.slot_budget = np.zeros(B, np.int32)
        # READ_DONE bits for admitted slots, one row per lane
        self.done_mask = np.zeros((self.n_lanes, self.lane_slots), bool)
        self.lane_head = np.zeros(self.n_lanes, np.int64)  # admission cursors
        self.lane_tail = np.zeros(self.n_lanes, np.int64)  # release cursors
        self._staged: List = []
        self._staged_lock = threading.Lock()
        self._stop = threading.Event()
        self.results: List[RequestResult] = []
        self.release_events: List[int] = []  # run lengths (diagnostics)

    # ------------------------------------------------------------------
    # ingestion worker: claim -> prefill -> stage
    # ------------------------------------------------------------------
    def _make_batch(self, req: Request):
        tokens = jnp.asarray(req.prompt, jnp.int32)[None, :]
        batch = {"tokens": tokens}
        if self.cfg.cross_attn_every:
            batch["image_embeds"] = jnp.zeros(
                (1, self.cfg.n_image_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.is_encdec:
            batch["audio_embeds"] = jnp.zeros(
                (1, self.cfg.enc_len, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return batch

    def _worker_loop(self, wid: int):
        while not self._stop.is_set():
            claim = self.sched.claim(wid, self.ecfg.claim_batch)
            if claim is None:
                time.sleep(0.0005)
                continue
            for req in claim.payloads:
                if req is None:
                    continue
                cache1, logits = self._prefill(self.params, self._make_batch(req))
                first = int(jnp.argmax(logits[0])) if self.ecfg.greedy else 0
                rr = RequestResult(
                    rid=req.rid, tokens=[first], t_arrival=req.t_arrival,
                    t_first_token=time.perf_counter(), worker=wid,
                )
                with self._staged_lock:
                    self._staged.append((cache1, rr, req.max_new_tokens))
            self.sched.complete(wid, claim)

    # ------------------------------------------------------------------
    # slot ring: release (TAIL advance) + admit (HEAD advance)
    # ------------------------------------------------------------------
    @property
    def head(self) -> int:
        """Total admissions across lanes (monotonic)."""
        return int(self.lane_head.sum())

    @property
    def tail(self) -> int:
        """Total releases across lanes (monotonic)."""
        return int(self.lane_tail.sum())

    def _release(self):
        """Advance every lane's tail over its contiguous done prefix
        (paper line 37-41) — ONE batched kernel launch for all R lanes."""
        if not self.ecfg.contiguous_release:
            return  # free-list mode: no tail semantics
        n = self.lane_slots
        in_flight = self.lane_head - self.lane_tail
        if not in_flight.any():
            return
        runs = np.asarray(ops.done_prefix_batch(
            jnp.asarray(self.done_mask),
            jnp.asarray((self.lane_tail % n).astype(np.int32)),
            jnp.asarray(in_flight.astype(np.int32)),
            impl="pallas", interpret=not ops.on_tpu(),
        ))
        for r in range(self.n_lanes):
            run = int(runs[r])
            if run:
                for i in range(run):
                    self.done_mask[r, (self.lane_tail[r] + i) % n] = False
                self.lane_tail[r] += run
                self.release_events.append(run)

    def _capacity_slots(self) -> List[int]:
        if self.ecfg.contiguous_release:
            self._release()
            n = self.lane_slots
            slots = []
            lane_free = n - (self.lane_head - self.lane_tail)
            # round-robin over lanes so admissions spread the straggler risk
            for i in range(n):
                for r in range(self.n_lanes):
                    if i < lane_free[r]:
                        slots.append(r * n + int((self.lane_head[r] + i) % n))
            return slots
        return [i for i in range(self.ecfg.n_slots) if self.slot_req[i] is None]

    def _insert(self, slot: int, cache1, rr: RequestResult, budget: int):
        B = self.ecfg.n_slots

        def put(cb, c1):
            axes = [i for i in range(cb.ndim)
                    if i < c1.ndim and c1.shape[i] == 1 and cb.shape[i] == B]
            ax = axes[0]
            start = [0] * cb.ndim
            start[ax] = slot
            return jax.lax.dynamic_update_slice(cb, c1.astype(cb.dtype), tuple(start))

        self.cache = jax.tree_util.tree_map(put, self.cache, cache1)
        self.slot_req[slot] = rr
        self.slot_budget[slot] = budget
        lane, off = slot // self.lane_slots, slot % self.lane_slots
        self.done_mask[lane, off] = False
        if self.ecfg.contiguous_release:
            self.lane_head[lane] += 1

    # ------------------------------------------------------------------
    def run(self, requests: List[Request], rate: Optional[float] = None,
            timeout: float = 180.0) -> List[RequestResult]:
        """Open loop: submit at ``rate`` req/s (None = all at once).

        Raises ``TimeoutError`` when ``timeout`` seconds pass before every
        request has finished; ``self.results`` keeps the finished ones.
        """
        threads = [
            threading.Thread(target=self._worker_loop, args=(w,), daemon=True)
            for w in range(self.ecfg.n_workers)
        ]
        for t in threads:
            t.start()

        def producer():
            interval = 1.0 / rate if rate else 0.0
            if interval:
                for req in requests:
                    req.t_arrival = time.perf_counter()
                    while not self.sched.submit(req):
                        time.sleep(0.0005)
                    time.sleep(interval)
            else:
                # burst mode: one descriptor burst + doorbell per chunk via
                # the schedulers' batch surface (prefix-retry on full ring)
                i = 0
                stamped = 0  # t_arrival once, at FIRST offer: admission
                # stalls must stay inside the measured request latency
                while i < len(requests):
                    chunk = requests[i : i + 64]
                    if i + len(chunk) > stamped:
                        now = time.perf_counter()
                        for req in requests[stamped : i + len(chunk)]:
                            req.t_arrival = now
                        stamped = i + len(chunk)
                    took = self.sched.submit_batch(chunk)
                    i += took
                    if took == 0:
                        time.sleep(0.0005)

        prod = threading.Thread(target=producer, daemon=True)
        prod.start()

        n_total = len(requests)
        deadline = time.perf_counter() + timeout
        while len(self.results) < n_total and time.perf_counter() < deadline:
            # 1) admit staged requests into released slots
            slots = self._capacity_slots()
            for slot in slots:
                with self._staged_lock:
                    item = self._staged.pop(0) if self._staged else None
                if item is None:
                    break
                self._insert(slot, *item)
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
            if not active:
                time.sleep(0.001)
                continue
            # 2) one batched decode step over all slots
            last = jnp.asarray(
                [r.tokens[-1] if r else 0 for r in self.slot_req], jnp.int32
            )[:, None]
            self.cache, logits = self._decode(self.params, self.cache, last)
            nxt = np.asarray(jnp.argmax(logits, -1))
            now = time.perf_counter()
            # 3) retire finished sequences (set READ_DONE bits)
            for i in active:
                rr = self.slot_req[i]
                rr.tokens.append(int(nxt[i]))
                self.slot_budget[i] -= 1
                if int(nxt[i]) == self.ecfg.eos_token or self.slot_budget[i] <= 0:
                    rr.t_done = now
                    self.results.append(rr)
                    self.slot_req[i] = None
                    self.done_mask[i // self.lane_slots, i % self.lane_slots] = True
        self._stop.set()
        self._release()  # hand back the trailing done-prefix (drain)
        for t in threads:
            t.join(timeout=2.0)
        if len(self.results) < n_total:
            raise TimeoutError(
                f"{len(self.results)}/{n_total} requests finished within "
                f"the {timeout}s deadline"
            )
        return list(self.results)
