"""The comparison that decides ``correct``.

Each check returns numbers, each with its limit; a run is correct when
every number is at or under its limit.  Every lane is held to the
guarantees of its configuration's scenario (``bench/scenarios/<name>.py``:
exactly-once, and TCP's delivery), and the kernel's prefix to the
plain prefix of the same claim words.  A sample of lanes, drawn from the
run seed with each policy's longest lane among them, is compared with
the plain reference simulation of the same lanes: for each statistic the
widest gap over the sample, and the median gap.  The widest gap swings
with rare float32 near-ties (two events a few ulps apart that the
program and the reference order differently, after which that lane's
schedules part); the median does not, so it carries the tight limit.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import manifest, reference

#: lanes of each policy the sampled comparison checks, the longest among them
SAMPLE_PER_POLICY = 8


@dataclass(frozen=True)
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def cat(res, field: str) -> np.ndarray:
    return np.concatenate([np.asarray(getattr(res[p], field)) for p in res.policies])


def sample_lanes(res, lanes: int, per_policy: int, seed: int) -> dict:
    """Lanes of each policy to compare: the one with the most claims,
    then lanes drawn from the run seed."""
    rng = np.random.default_rng(int(seed) % (1 << 63))
    picks = {}
    for p in res.policies:
        longest = int(np.argmax(np.asarray(res[p].batches)))
        rest = rng.choice(lanes, size=min(per_policy, lanes), replace=False)
        picks[p] = [longest] + [int(i) for i in rest if i != longest][: per_policy - 1]
    return picks


def lane_knobs(traffic: dict, point: dict) -> dict:
    knobs = {}
    for group in traffic.get("static", {}).values():
        knobs.update(group)
    knobs.update(point)
    return knobs


def reference_stats(built, config, traffic, picks, dtype=np.float32) -> dict:
    """The plain reference's results of each picked lane, in ``dtype``,
    from the configuration's scenario (``bench/scenarios/<name>.py``)."""
    lanes = sorted({i for idx in picks.values() for i in idx})
    one = manifest.scenario(config["scenario"]).lane_reference(
        built, config, traffic, lanes, dtype
    )
    row = {lane: j for j, lane in enumerate(lanes)}
    return {(p, i): one(p, i, row[i]) for p, idx in picks.items() for i in idx}


def program_stats(res, picks) -> dict:
    """The program's results of the picked lanes, in the same form."""
    fields = res[res.policies[0]]._fields
    return {
        (p, i): {f: np.asarray(getattr(res[p], f))[i] for f in fields}
        for p, idx in picks.items()
        for i in idx
    }


def gap(got, want, floor) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    with np.errstate(invalid="ignore"):
        g = np.abs(got - want)
        if floor is not None:
            g = g / np.maximum(np.abs(want), floor)
    # inf == inf (an unfinished flow on both sides) is no gap; a NaN or
    # one-sided inf answer is the widest
    g = np.where(got == want, 0.0, g)
    g = np.where(np.isnan(g), np.inf, g)
    return float(g.max()) if g.size else 0.0


def sampled_numbers(got: dict, want: dict, scenario) -> dict:
    """Widest and median gap of each compared quantity over the lanes;
    ``scenario.lane_gaps`` gives one lane's gaps."""
    per: dict = {}
    for key, w in want.items():
        for stat, g in scenario.lane_gaps(got[key], w).items():
            per.setdefault(stat, []).append(g)
    out = {}
    for f, gaps in per.items():
        out[f"{f}_gap"] = max(gaps)
        out[f"{f}_gap_median"] = float(np.median(gaps))
    return out


def exact_numbers(res, offered: np.ndarray) -> dict:
    """Exactly-once over every lane: popcount == prefix == items ==
    ``offered``, and the kernel's prefix against the plain prefix of
    the same claim words (each row capped at ``offered``)."""
    fields = ("claimed_popcount", "claimed_prefix", "items")
    pop, pre, items = (cat(res, f) for f in fields)
    bad = (pop != offered) | (pre != offered) | (items != offered)
    plain = reference.done_prefix(cat(res, "claimed_words"), offered)
    return {
        "exactly_once_bad_lanes": int(bad.sum()),
        "prefix_mismatch_lanes": int((plain != pre).sum()),
    }


def numbers(built, res, config, traffic, seed: int, ref=None, got=None) -> dict:
    """Every compared number of one run (values only).

    ``ref`` / ``got`` replace the reference's or the program's sampled
    results (the control puts the lower-precision reference in the
    program's place through ``got``)."""
    scenario = manifest.scenario(config["scenario"])
    vals = scenario.guarantee_numbers(built, res, config)
    picks = sample_lanes(res, built.lanes_per_policy, SAMPLE_PER_POLICY, seed)
    if ref is None:
        ref = reference_stats(built, config, traffic, picks)
    if got is None:
        got = program_stats(res, picks)
    vals.update(sampled_numbers(got, ref, scenario))
    return vals


def check(built, res, config, traffic, cell_limits, seed) -> list:
    """The run's numbers, each beside its limit from the cell's file;
    a number the file lists under ``not_compared`` (one whose sound and
    control readings no limit separates) is left out."""
    vals = numbers(built, res, config, traffic, seed)
    lim = cell_limits["limits"]
    skip = set(cell_limits.get("not_compared", ()))
    missing = sorted(set(vals) - set(lim) - skip)
    if missing:
        raise KeyError(f"limits file lacks {missing}")
    kept = [k for k in vals if k not in skip]
    return [Number(k, float(vals[k]), float(lim[k])) for k in kept]
