"""One request surface for every vectorized sweep: SweepRequest in,
SweepResult out.

The jax plane grew one entry point per scenario (``sweep_forwarder_jax``,
``sweep_policy_jax``, ``sweep_tcp_jax``, ``run_lanes_fused``,
``fused_jax_requests``), each with its own calling convention.  This
module unifies them: a :class:`SweepRequest` names the scenario, the
policies, the lane grid (knob dicts whose array values are sweep axes),
the arrival process, the engine and its sharding — and
:func:`run_sweep` builds the per-policy segments, runs them in ONE
jitted call on the claim-compacted engine, and returns a
:class:`SweepResult` keyed by policy name.

The old entry points remain as thin shims that emit
``DeprecationWarning`` and forward verbatim — same lanes, same results,
bit for bit (pinned by ``tests/test_sweep_api.py``).  Migration map::

    sweep_forwarder_jax(pol, ...)  -> SweepRequest(scenario="forwarder",
                                                   policies=[pol], ...)
    sweep_policy_jax(pol, ...)     -> SweepRequest(scenario="queueing",
                                                   policies=[pol], ...)
    sweep_tcp_jax(pol, ...)        -> SweepRequest(scenario="tcp",
                                                   policies=[pol], ...)
    run_lanes_fused(requests, ...) -> SweepRequest(policies=[...], ...)
                                      (one segment per policy)
    fused_jax_requests(seeds, ...) -> handled inside run_sweep

Scenario -> model mapping:

===========  =========================================================
forwarder    open-loop L3 forwarder (sec 4.3.1): per-size lognormal
             service, ``arrival`` picks the process (poisson / bursty
             MAWI mix / diurnal).
queueing     M/G/N vs N x M/G/1 (sec 3.2): Poisson arrivals, ``service``
             picks M / D / LN.
tcp          closed-loop NewReno/CUBIC lanes over the forwarder
             (sec 4.3.2) on :mod:`repro.core.tcpjax`; ``tcp_params``
             additionally takes ``sack`` (scoreboard multi-hole
             recovery, static per request), ``send_burst`` (events
             coalesced per scan step), ``loss_every`` (deterministic
             drop-once receiver loss), ``loss_rate`` / ``loss_burst``
             (random Bernoulli / Gilbert-Elliott-style burst loss,
             sweepable, counter-based RNG shared with the DES mirror)
             and ``pkt_budget`` (per-lane elephant/mice packet cap,
             sweepable).
serving      open-loop SLO sweeps (:mod:`repro.core.servingjax`):
             heavy-tailed sessions, admission + autoscale knobs from
             :class:`~repro.core.jaxplane.ServingParams` (including
             the sweepable ``drop_rate`` response loss); each policy's
             registry ``serving_defaults`` seed the knobs and the
             request's ``serving_params`` override them key-wise.
             Overload-control statics (client ``timeout`` / ``retries``
             / ``backoff`` / ``jitter`` / ``hedge``, breaker
             ``breaker_age``, latency-reactive ``scale_latency`` — see
             :class:`~repro.core.jaxplane.OverloadConfig`) ride in
             ``serving_params`` too and are popped per request before
             the sweepable knobs are broadcast.
===========  =========================================================
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

from . import record
from .policy import _fused_requests, get_spec, jax_policies
from .servingjax import ARRIVAL_WORKLOADS

__all__ = ["SweepRequest", "SweepResult", "run_sweep"]


@dataclass(frozen=True)
class SweepRequest:
    """A full sweep, declaratively: what to simulate, for whom, and how.

    Knob-dict values may be scalars (broadcast to every lane) or
    [lanes]-shaped arrays (a sweep axis); ``seeds`` defines the lane
    count per policy segment.  ``n_packets`` is the per-lane load for
    closed scenarios and the generation *capacity* for ``serving``
    (the per-lane ``horizon`` in ``serving_params`` decides how much of
    it is offered).
    """

    scenario: str = "forwarder"  # forwarder | queueing | tcp | serving
    policies: Optional[Sequence[str]] = None  # None = every jax-capable policy
    seeds: Any = (0,)
    arrival: str = "poisson"  # poisson | bursty | diurnal
    service: Optional[str] = None  # service kind override (fwd/M/D/LN/HT)
    lane_params: Mapping[str, Any] = field(default_factory=dict)
    traffic_params: Mapping[str, Any] = field(default_factory=dict)
    fault_params: Mapping[str, Any] = field(default_factory=dict)
    serving_params: Mapping[str, Any] = field(default_factory=dict)
    tcp_params: Mapping[str, Any] = field(default_factory=dict)
    #: per-lane load / generation capacity; for ``tcp`` an int (one
    #: flow) or a per-flow packet-count array (flow layout)
    n_packets: Any = 2000
    n_workers: int = 4
    max_batch: int = 64
    n_flows: int = 256
    t_start: Any = None  # tcp only: per-flow start times
    tx_budget: Optional[int] = None  # tcp only: transmission budget
    n_steps: Optional[int] = None  # tcp only: event budget
    engine: str = "compacted"
    shards: Union[int, str] = 1
    chunk: int = 64
    claim_budget: Optional[int] = None
    prefix_impl: str = "auto"
    prefix_interpret: bool = False
    return_times: bool = False
    #: merge each policy's registry ``serving_defaults`` under the
    #: request's ``serving_params`` (serving scenario only)
    use_policy_serving_defaults: bool = True


@dataclass(frozen=True)
class SweepResult:
    """Per-policy lane results of one fused call, in request order.

    ``lanes[name]`` is a :class:`~repro.core.jaxplane.LaneResult`
    (or :class:`~repro.core.tcpjax.TcpLaneResult` for the tcp
    scenario), device arrays that may still be computing.  Besides the
    simulated statistics each lane carries two int32 scan counters:
    ``active_steps``, the steps taken before the lane was done, and
    ``scan_steps``, the steps its policy segment's scan ran (chunks
    that ran times ``chunk``; the shard's own when ``shards > 1``).
    ``timings`` carries ``compile_s`` / ``run_s`` and the compiled
    program's ``mosaic_kernels`` count when the caller asked for them.
    """

    request: SweepRequest
    policies: Tuple[str, ...]
    lanes: Mapping[str, Any]

    def __getitem__(self, policy: str):
        return self.lanes[policy]

    timings: Mapping[str, float] = field(default_factory=dict)


def _serving_knobs(req: SweepRequest, name: str) -> dict:
    base = (
        dict(get_spec(name).serving_defaults)
        if req.use_policy_serving_defaults
        else {}
    )
    base.update(req.serving_params)
    return base


def run_sweep(request: SweepRequest, timings: dict | None = None) -> SweepResult:
    """Run every (policy, lane) of a :class:`SweepRequest` in one jitted
    call and return a :class:`SweepResult` keyed by policy name.

    Imports the jax engines lazily so the module stays importable on
    DES-only hosts; ``timings`` (a dict, filled in place and echoed on
    the result) reports AOT compile/run seconds and the compiled
    program's Pallas kernel count.

    The call is instrumented (see README "Reading a sweep from the
    inside"): profiler spans ``repro.sweep`` around it, with
    ``repro.prepare`` and ``repro.dispatch`` inside; name scopes on the
    device ops; set-up phases of each compiled program and the scan
    counters of the last call in :mod:`repro.core.record`.
    """
    from jax import profiler

    with profiler.TraceAnnotation("repro.sweep"):
        result = _run_sweep(request, timings)
    record.note_sweep(result.lanes)
    return result


def _run_sweep(req: SweepRequest, timings: dict | None) -> SweepResult:
    from jax import profiler

    names = list(req.policies) if req.policies is not None else jax_policies()
    if req.scenario in ("forwarder", "queueing", "serving"):
        from .jaxplane import _fused_lanes

        serving = req.scenario == "serving"
        if req.scenario == "queueing":
            workload, service = "udp", req.service or "M"
        else:
            workload = ARRIVAL_WORKLOADS[req.arrival]
            service = req.service or ("HT" if serving else "fwd")
        with profiler.TraceAnnotation("repro.prepare"):
            reqs = _fused_requests(
                req.seeds,
                lane_params=dict(req.lane_params),
                policies=names,
                traffic_params=dict(req.traffic_params),
                fault_params=dict(req.fault_params),
            )
            if serving:
                for r in reqs:
                    r["serving_params"] = _serving_knobs(req, r["policy"])
        results = _fused_lanes(
            reqs,
            workload=workload,
            service=service,
            n_packets=req.n_packets,
            n_workers=req.n_workers,
            max_batch=req.max_batch,
            n_flows=req.n_flows,
            engine=req.engine,
            serving=serving,
            claim_budget=req.claim_budget,
            chunk=req.chunk,
            shards=req.shards,
            prefix_impl=req.prefix_impl,
            prefix_interpret=req.prefix_interpret,
            return_times=req.return_times,
            timings=timings,
        )
    elif req.scenario == "tcp":
        from .tcpjax import run_tcp_lanes_fused

        with profiler.TraceAnnotation("repro.prepare"):
            reqs = _fused_requests(
                req.seeds,
                lane_params=dict(req.lane_params),
                policies=names,
                tcp_params=dict(req.tcp_params),
                fault_params=dict(req.fault_params),
            )
        results = run_tcp_lanes_fused(
            reqs,
            n_pkts=req.n_packets,
            t_start=req.t_start,
            n_workers=req.n_workers,
            max_batch=req.max_batch,
            tx_budget=req.tx_budget,
            n_steps=req.n_steps,
            engine=req.engine,
            chunk=req.chunk,
            shards=req.shards,
            prefix_impl=req.prefix_impl,
            prefix_interpret=req.prefix_interpret,
            timings=timings,
        )
    else:
        raise ValueError(
            f"unknown scenario {req.scenario!r}; "
            "expected forwarder | queueing | tcp | serving"
        )
    return SweepResult(
        request=replace(req, policies=tuple(names)),
        policies=tuple(names),
        lanes=dict(zip(names, results)),
        timings=dict(timings or {}),
    )
