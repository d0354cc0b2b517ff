"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching engine (COREC or RSS ingestion) over a
synthetic request stream and prints TTFT / completion-latency stats.
"""

from __future__ import annotations

import argparse

import numpy as np

from .. import configs
from ..serving import EngineConfig, InferenceEngine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=configs.ALL_ARCHS)
    ap.add_argument("--policy", default="corec", choices=["corec", "rss"])
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=None, help="req/s (open loop)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = configs.get_tiny(args.arch)
    ecfg = EngineConfig(n_slots=args.slots, max_seq=64, n_workers=args.workers,
                        policy=args.policy, eos_token=-1)
    eng = InferenceEngine(cfg, ecfg)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=list(rng.integers(2, cfg.vocab, 8)),
                max_new_tokens=args.new_tokens, session=int(rng.integers(0, 8)))
        for i in range(args.requests)
    ]
    res = eng.run(reqs, rate=args.rate)
    ttft = np.array([r.ttft for r in res])
    lat = np.array([r.latency for r in res])
    print(f"[serve] {cfg.name} policy={args.policy}: {len(res)} requests done")
    ttft_p99 = np.percentile(ttft, 99) * 1e3
    lat_p99 = np.percentile(lat, 99) * 1e3
    print(f"  ttft   mean={ttft.mean() * 1e3:.1f}ms p99={ttft_p99:.1f}ms")
    print(f"  latency mean={lat.mean() * 1e3:.1f}ms p99={lat_p99:.1f}ms")
    return res


if __name__ == "__main__":
    main()
