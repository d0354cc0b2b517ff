"""Golden digests of every fused-sweep result field.

Run from the repository root as
``PYTHONPATH=src python tests/golden/_capture_lane_fields.py``.  The
digests in ``lane_fields.json`` were captured on the tree before the
scan counters (``active_steps`` / ``scan_steps``) joined
``LaneResult`` / ``TcpLaneResult``; ``tests/test_scan_counters.py``
replays the same sweeps and checks every field captured then is still
bit for bit the same.  Regenerate only for a deliberate change of
results, and say so.
"""

from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np

GOLDEN = Path(__file__).resolve().parent / "lane_fields.json"


def requests() -> dict:
    """Small sweeps over every engine path the counters touch."""
    from repro.core import SweepRequest

    return {
        "forwarder": SweepRequest(
            seeds=np.arange(6),
            n_packets=300,
            lane_params=dict(
                batch=np.array([1, 2, 4, 8, 16, 32]), deschedule_prob=2e-3
            ),
        ),
        "forwarder_faults": SweepRequest(
            arrival="bursty",
            seeds=np.arange(4),
            n_packets=250,
            lane_params=dict(batch=16),
            fault_params=dict(crash_worker=1, crash_t=20.0, lease=5.0),
        ),
        "serving_overload": SweepRequest(
            scenario="serving",
            arrival="diurnal",
            seeds=np.arange(3),
            n_packets=200,
            traffic_params=dict(rate=4.0),
            serving_params=dict(horizon=60.0, slo_target=20.0, timeout=30.0, retries=1),
        ),
        "tcp": SweepRequest(
            scenario="tcp",
            seeds=np.arange(4),
            n_packets=[40, 40],
            t_start=[0.0, 13.0],
            lane_params=dict(deschedule_prob=2e-3),
        ),
        "tcp_sack": SweepRequest(
            scenario="tcp",
            seeds=np.arange(3),
            n_packets=[30, 30],
            tcp_params=dict(sack=True, loss_rate=0.03),
        ),
        "forwarder_reference": SweepRequest(
            seeds=np.arange(3), n_packets=200, engine="reference"
        ),
    }


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    head = f"{a.dtype.str}{a.shape}".encode()
    return hashlib.sha256(head + a.tobytes()).hexdigest()[:20]


def digests(result) -> dict:
    return {
        f"{policy}/{field}": digest(getattr(result[policy], field))
        for policy in result.policies
        for field in result[policy]._fields
    }


def main() -> None:
    from repro.core import run_sweep

    out = {name: digests(run_sweep(req)) for name, req in requests().items()}
    GOLDEN.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    print(f"wrote {GOLDEN}")


if __name__ == "__main__":
    main()
