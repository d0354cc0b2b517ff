"""The serving cell on the CPU at a small size: sound runs pass, the
control and planted faults read ``correct`` false, and the readers of
the ``serve`` and ``lane_setup`` scopes."""

import dataclasses

import faults
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
from harness import correct, manifest, opscopes, serving_reference, sweep
from repro.core import jaxplane, run_sweep

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def cell():
    return faults.small_cell("serving-diurnal", capacity=128)


def halve_lanes(orig):
    """A lane engine that simulates only the first half of each
    segment's lanes, serving knobs cut too, and hands their results
    back for the second half."""

    def half(requests, **kw):
        requests = list(requests)
        lanes = len(np.asarray(requests[0]["seeds"]))
        keep = lanes // 2

        def cut(v):
            a = np.asarray(v)
            return a[:keep] if a.ndim and a.shape[0] == lanes else v

        small = []
        for req in requests:
            r = dict(req, seeds=np.asarray(req["seeds"])[:keep])
            for key in ("lane_params", "traffic_params", "fault_params",
                        "serving_params"):
                if r.get(key):
                    r[key] = {k: cut(v) for k, v in r[key].items()}
            small.append(r)
        outs = orig(small, **kw)

        def widen(a):
            a = np.asarray(a)
            return np.concatenate([a, a[: lanes - keep]])

        return [jax.tree_util.tree_map(widen, o) for o in outs]

    return half


def _altered(monkeypatch, field, delta):
    """The program's ``field`` output moved by ``delta`` on each
    segment's first lane, where it is produced."""
    orig = jaxplane._segment_outputs

    def moved(*args, **kw):
        out = orig(*args, **kw)
        return dict(out, **{field: out[field].at[0].add(delta)})

    monkeypatch.setattr(jaxplane, "_segment_outputs", moved)


def test_sound_run_is_correct(monkeypatch, cell):
    out = faults.run_small(monkeypatch, cell, SEED)
    assert out["correct"], faults.failed(out)
    assert out["checks"]["window_compilations"]["value"] == 0


def test_every_policy_agrees_with_the_reference(cell):
    built = sweep.build(cell.config, cell.traffic, SEED)
    res = run_sweep(built.request)
    picks = correct.sample_lanes(res, built.lanes_per_policy, 4, SEED)
    ref = correct.reference_stats(built, cell.config, cell.traffic, picks)
    got = correct.program_stats(res, picks)
    lim = cell.limits["limits"]
    skip = set(cell.limits.get("not_compared", ()))
    for p in res.policies:
        one = {k: v for k, v in ref.items() if k[0] == p}
        nums = correct.sampled_numbers(got, one, manifest.scenario("serving"))
        assert [k for k, v in nums.items() if k not in skip and v > lim[k]] == [], p


def test_admission_sheds_and_locked_differs_from_corec(cell):
    built = sweep.build(cell.config, cell.traffic, SEED)
    res = run_sweep(built.request)
    admit = np.array([pt[0]["admit_limit"] for pt in built.points])
    for p in res.policies:
        assert np.asarray(res[p].shed)[admit == 16].sum() > 0, p
    a, b = res["locked"], res["corec"]
    assert any(not np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))
               for f in ("p50", "p99", "mean", "slo_attained", "shed"))


def test_control_fails_where_the_program_passes(cell):
    jax.clear_caches()
    line = control.readings(cell, 2**31 + 7, control=True)
    limits = cell.limits["limits"]
    skip = set(cell.limits.get("not_compared", ()))

    def over(nums):
        return [k for k, v in nums.items() if k not in skip and v > limits[k]]

    assert over(line["sound"]) == []
    assert over(line["control"])


def test_shed_altered_where_it_is_produced(monkeypatch, cell):
    _altered(monkeypatch, "shed", 1)
    out = faults.run_small(monkeypatch, cell, SEED)
    assert not out["correct"]
    assert "exactly_once_bad_lanes" in faults.failed(out)


def test_offered_off_by_one(monkeypatch, cell):
    _altered(monkeypatch, "offered", -1)
    out = faults.run_small(monkeypatch, cell, SEED)
    assert not out["correct"]
    assert "offered_bad_lanes" in faults.failed(out)


def test_half_of_the_lanes_left_out(monkeypatch, cell):
    monkeypatch.setattr(jaxplane, "_fused_lanes", halve_lanes(jaxplane._fused_lanes))
    out = faults.run_small(monkeypatch, cell, SEED)
    assert not out["correct"]
    assert any(k.endswith("_gap") for k in faults.failed(out))


#: lanes of the whole cell, in run seed 0's order, whose plain gaps are
#: among the widest on the CPU (p99 0.79, counts 3.5, batches 0.23): one
#: near-tie decides a different claim, and admission carries it on
WIDEST = [("adaptive-batch", 487), ("hybrid", 1697), ("corec", 3776)]


def _program_inputs(policy, config, traffic, knobs, lane_seed):
    """One lane's float32 arrival times and per-queue service prefix
    sums as the program makes them (``_gen_traffic``, then
    ``_lane_setup``'s rank-order rows and ``cumsum``)."""
    n, w = int(config["capacity"]), int(config["n_workers"])
    fields = jaxplane.TrafficParams._fields
    tp = jaxplane.TrafficParams(**{
        k: jnp.float32(v) for k, v in jaxplane.default_traffic_params(
            **{k: knobs[k] for k in fields if k in knobs}).items()})
    kt, _ = jax.random.split(jax.random.PRNGKey(np.uint32(lane_seed)))
    arr, svc, flows = jaxplane._gen_traffic(
        kt, tp, traffic["arrival"], config["service"], n, int(config["n_flows"]))
    qid = np.asarray(jaxplane.build_policy(policy).select_queue(flows, w))
    rows = np.zeros((w, n), np.float32)
    for v in range(w):
        m = np.flatnonzero(qid == v)
        rows[v, : m.size] = np.asarray(svc)[m]
    return {"arr": np.asarray(arr), "cumsvc": np.asarray(jnp.cumsum(jnp.asarray(rows), axis=1))}


def test_widest_gaps_close_on_the_programs_own_inputs():
    """The reference's claim model agrees with the program's: on the
    lanes with the widest gaps, the reference fed the program's float32
    arrival times and prefix sums reads the program's statistics."""
    full = manifest.load_cell("serving-diurnal")
    cfg, mix = full.config, full.traffic
    built = sweep.build(cfg, mix, 0)
    req, lanes = built.request, sorted({lane for _, lane in WIDEST})
    total = built.lanes_per_policy

    def cut(v):
        a = np.asarray(v)
        return a[lanes] if a.ndim and a.shape[0] == total else v

    small = dataclasses.replace(
        req, seeds=np.asarray(req.seeds)[lanes],
        **{g: {k: cut(v) for k, v in getattr(req, g).items()}
           for g in ("lane_params", "traffic_params", "serving_params")})
    res = run_sweep(small)
    draws = serving_reference.serving_draws(
        np.asarray(req.seeds)[lanes], int(cfg["capacity"]), int(cfg["n_flows"]),
        -(-int(cfg["capacity"]) // int(req.chunk)) * int(req.chunk))
    scen = manifest.scenario("serving")
    for policy, lane in WIDEST:
        j = lanes.index(lane)
        knobs = correct.lane_knobs(mix, built.points[lane][0])
        got = {f: np.asarray(getattr(res[policy], f))[j] for f in res[policy]._fields}
        one = {k: v[j] for k, v in draws.items()}
        args = (policy, knobs, one, int(cfg["n_workers"]), int(cfg["max_batch"]))
        plain = scen.lane_gaps(got, serving_reference.serving_lane(*args))
        given = _program_inputs(policy, cfg, mix, knobs, np.asarray(req.seeds)[lane])
        fed = scen.lane_gaps(got, serving_reference.serving_lane(*args, given=given))
        assert max(plain.values()) > 0.2, (policy, lane, plain)
        assert max(fed.values()) < 1e-5, (policy, lane, fed)


@pytest.mark.parametrize("rate", [2.0, 5.0])
def test_diurnal_arrivals_invert_the_intensity(rate):
    s = np.cumsum(np.random.default_rng(3).exponential(size=500))
    t = serving_reference.diurnal_arrivals(s, rate, 0.6, 50.0)
    w = 2 * np.pi / 50.0
    big = rate * (t + 0.6 / w * (1 - np.cos(w * t)))
    np.testing.assert_allclose(big, s, rtol=0, atol=1e-9)
    assert (np.diff(t) > 0).all()


@pytest.mark.parametrize(
    "op_name,name,want",
    [
        ("jit(f)/seg.corec/scan/chunk.64/while/body/closed_call/vmap(serve)/gather",
         "serve", 1.0),
        ("jit(f)/seg.corec/lane_setup/vmap()/cumsum", "lane_setup", 1.0),
        ("jit(f)/seg.corec/lane_setup/vmap()/cumsum", "serve", 0.0),
        ("jit(f)/seg.corec/scan/vmap(serve)/max/jit(f)/seg.corec/scan/vmap(min)",
         "serve", 0.5),
        ("jit(f)/seg.corec/scan/observe/add", "serve", 0.0),
        ("", "serve", 0.0),
    ],
)
def test_scope_share_of_an_op(op_name, name, want):
    assert opscopes.share(op_name, name) == want


def _events(ops, calls=((0.0, 100.0),)):
    return {
        "ops": [["/device:TPU:0", "op", s, e - s, n] for s, e, n in ops],
        "spans": [["bench.call", s, e] for s, e in calls],
    }


def test_scope_time_is_own_time_in_the_window():
    serve = "jit(f)/seg.corec/scan/chunk.8/while/body/vmap(serve)/gather"
    setup = "jit(f)/seg.corec/lane_setup/vmap()/add"
    loop = "jit(f)/seg.corec/scan/chunk.8/while"
    red = opscopes.reduce(_events([
        (0.0, 10.0, setup),
        (10.0, 60.0, loop),  # encloses the body ops below
        (12.0, 20.0, serve),
        (20.0, 22.0, ""),  # no metadata: takes the loop's name, not serve's
        (30.0, 40.0, serve),
        (95.0, 110.0, setup),  # cut at the window's end
    ]))
    assert red["n_calls"] == 1
    assert red["scope_s"]["serve"] == pytest.approx(18e-9)
    assert red["scope_s"]["lane_setup"] == pytest.approx(15e-9)


def test_scope_ms_absent_without_the_scope(tmp_path):
    ctx = {"trace": {"window_s": 1e-7}, "opscopes": opscopes.reduce(
        _events([(0.0, 50.0, "jit(f)/seg.corec/scan/chunk.8/while")]))}
    assert opscopes.scope_ms(ctx, "serve") is None
    assert opscopes.scope_ms(ctx, "lane_setup") is None
    assert opscopes.of_run({"trace": {"window_s": 1.0}}, tmp_path) is None
