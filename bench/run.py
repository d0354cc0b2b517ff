"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, timed from process start): imports, the cell's
``SweepRequest`` built from its data files, and one warm-up call of
``repro.core.run_sweep`` that compiles the cell's fused program, or
loads it from the persistent compile cache at ``bench/.jax_cache``.
The window then calls ``run_sweep`` on the same request, each call
ending in ``block_until_ready``, until ``--seconds`` have passed.
``sim_pkts_per_s`` is the packets those calls' requests offer over the
window's wall time.  With ``--trace 1`` the window is one call under
the profiler, and the per-layer metrics are read from that trace.
Correctness is checked on the last call's results after the window.

The run exits non-zero with no result line when JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu writes its log files under /tmp unless told otherwise; a run
# writes only inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from harness import manifest  # noqa: E402

#: the persistent compile cache: a fixed path inside the checkout, so
#: every run of a cell after the first loads its program from here
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
#: JAX monitoring events that mean something was compiled or loaded
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    log(f"device: platform={platform} kind={kind} count={count}")
    info = dict(platform=platform, kind=kind, count=count)
    if platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {platform})")
    if count < chips:
        raise NoChip(f"cell needs {chips} chips, found {count}")
    return info


class EventCounter:
    """Counts JAX monitoring events (compiles, traces, cache use)."""

    def __init__(self):
        import jax.monitoring as mon

        self.counts: dict = {}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] = self.counts.get(name, 0) + 1

    def _duration(self, name, _secs, **_):
        self.counts[name] = self.counts.get(name, 0) + 1

    def take(self) -> dict:
        out, self.counts = self.counts, {}
        return out


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, the small host-side ones included, so a
    # warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def one_call(request):
    import jax

    from repro.core import run_sweep

    res = run_sweep(request)
    jax.block_until_ready(res.lanes)
    return res


def batches_summary(res) -> tuple:
    import numpy as np

    b = np.concatenate([np.asarray(res[p].batches) for p in res.policies])
    return float(b.mean()), int(b.max())


def peak_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def window(request, seconds: float) -> tuple:
    """Calls until ``seconds`` have passed: (last result, calls, wall s)."""
    t0 = time.perf_counter()
    calls = 0
    while True:
        res = one_call(request)
        calls += 1
        wall = time.perf_counter() - t0
        if wall >= seconds:
            return res, calls, wall


def traced_call(request) -> tuple:
    """One call under the profiler, inside the benchmark's host spans:
    (its result, the trace's plain events)."""
    import jax

    from harness import trace
    from repro.core import run_sweep

    with trace.capture(TRACE_DIR) as cap:
        with jax.profiler.TraceAnnotation("bench.call"):
            res = run_sweep(request)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(res.lanes)
    return res, trace.load(cap.path)


def run_cell(cell, seed: int, seconds: float, traced: bool) -> dict:
    """One run of ``cell``: the result dict of the output line."""
    from harness import correct, sweep

    dev = device_info(cell.chips)
    counter = EventCounter()
    enable_cache()
    built = sweep.build(cell.config, cell.traffic, seed)
    log(
        f"cell {cell.name}: lanes {built.lanes} ({built.lanes_per_policy} per policy), "
        f"packets per call {built.packets_per_call}"
    )
    res = one_call(built.request)
    setup_s = time.perf_counter() - T_PROCESS
    ev = counter.take()
    log(
        f"setup: {setup_s:.3f} s, compile cache hits {ev.get(CACHE_HIT, 0)} "
        f"misses {ev.get(CACHE_MISS, 0)}, programs compiled or loaded "
        f"{ev.get(COMPILE_EVENT, 0)}"
    )
    out = dict(attempted=0, failed=0, metrics={}, device=dev)
    if traced:
        from harness import trace

        calls = 1
        res, events = traced_call(built.request)
        reduced = trace.reduce(events)
        words = [res[p].claimed_words.shape for p in res.policies]
        ctx = dict(
            trace=reduced,
            scenario=cell.config["scenario"],
            words_shape=(sum(w[0] for w in words), words[0][1]),
            peak_bytes=peak_bytes(),
            peaks=trace.device_peaks(dev["kind"]),
        )
        for m in cell.per_layer:
            value = manifest.metric_reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = dict(value=value, unit=m["unit"])
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = trace.breakdown(reduced)
    else:
        res, calls, wall = window(built.request, seconds)
        rate = sweep.sim_pkts_per_s(built.packets_per_call, calls, wall)
        values = dict(sim_pkts_per_s=rate, setup_s=setup_s)
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
        log(f"window: {calls} calls in {wall:.6f} s, {rate:.3f} pkts/s")
    ev = counter.take()
    compiled = ev.get(COMPILE_EVENT, 0)
    log(f"window: compilations {compiled}, jaxpr traces {ev.get(TRACE_EVENT, 0)}")
    b_mean, b_max = batches_summary(res)
    log(f"claims per lane (scan steps): mean {b_mean:.3f} max {b_max}")
    dev["memory_peak_bytes"] = peak_bytes()
    numbers = correct.check(built, res, cell.config, cell.traffic, cell.limits, seed)
    numbers.append(correct.Number("window_compilations", compiled, 0))
    out.update(
        correct=all(n.ok for n in numbers),
        attempted=calls,
        failed=0,
        checks={n.name: dict(value=n.value, limit=n.limit) for n in numbers},
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        value, limit = c["value"], c["limit"]
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    keys = ("correct", "attempted", "failed", "metrics", "device", "breakdown")
    keys += ("checks",)
    print(json.dumps({k: out[k] for k in keys if k in out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
