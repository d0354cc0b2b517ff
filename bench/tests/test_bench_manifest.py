"""The benchmark's manifest, data files and peak table."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import manifest, trace  # noqa: E402
from harness.manifest import ManifestError  # noqa: E402


@pytest.mark.parametrize(
    "name", ["fwd-udp", "scan_ms.fwd", "l3fwd-4w", "_x", "9lives", "a" * 64]
)
def test_valid_names(name):
    assert manifest.check_name(name, "t") == name


@pytest.mark.parametrize(
    "name", ["", "-x", ".x", "a b", "a,b", "a/b", "a" * 65, "µs", "a\tb", None]
)
def test_invalid_names_refused(name):
    with pytest.raises(ManifestError):
        manifest.check_name(name, "t")


@pytest.mark.parametrize("unit", ["pkts/s", "%", "ms", "MB", "us", "a" * 16])
def test_valid_units(unit):
    assert manifest.check_unit(unit, "m") == unit


@pytest.mark.parametrize("unit", ["", "pkts per s", "a" * 17, "µs"])
def test_invalid_units_refused(unit):
    with pytest.raises(ManifestError):
        manifest.check_unit(unit, "m")


def test_committed_manifest_meets_the_contract():
    man = manifest.load_manifest()
    assert man["paths"] == ["bench"]
    assert man["command"][1] == "bench/run.py"
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    for w in man["workloads"] + man["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(json.dumps(man)) < 64 * 1024


def test_every_cell_loads_its_files_by_name():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["scenario"] == cell.config["scenario"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "sim_pkts_per_s"}
        assert cell.per_layer, w["name"]


def test_every_per_layer_metric_has_a_reader():
    for m in manifest.load_manifest()["per_layer"]:
        assert callable(manifest.metric_reader(m["name"]))


def test_unknown_metric_reader_refused():
    with pytest.raises(ManifestError):
        manifest.metric_reader("no_such_metric")


def _mini_root(tmp_path, traffic_scenario="forwarder"):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "limits").mkdir()
    (tmp_path / "bench" / "scenarios").mkdir()
    (tmp_path / "bench/scenarios/forwarder.py").write_text("KNOB_GROUPS = ()\n")
    man = {
        "configs": [
            {"name": "cfg-a", "file": "bench/configs/cfg-a.json", "reduced": []}
        ],
        "workloads": [
            {"name": "cell-a", "config": "cfg-a", "traffic": "mix-a", "chips": 1}
        ],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower"},
            {"name": "rate_x", "unit": "x/s", "better": "higher",
             "workloads": ["cell-a"]},
        ],
        "per_layer": [
            {"name": "m.a", "unit": "ms", "better": "lower", "moves": "rate_x"}
        ],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    (tmp_path / "bench/configs/cfg-a.json").write_text(
        json.dumps({"name": "cfg-a", "scenario": "forwarder"})
    )
    (tmp_path / "bench/traffic/mix-a.json").write_text(
        json.dumps({"scenario": traffic_scenario})
    )
    (tmp_path / "bench/limits/cell-a.json").write_text(json.dumps({"limits": {}}))
    return man


def test_discovery_by_name(tmp_path):
    _mini_root(tmp_path)
    cell = manifest.load_cell("cell-a", root=tmp_path)
    assert cell.config["scenario"] == "forwarder"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "rate_x"]
    assert [m["name"] for m in cell.per_layer] == ["m.a"]
    with pytest.raises(ManifestError):
        manifest.load_cell("cell-b", root=tmp_path)


def test_missing_or_mismatched_files_refused(tmp_path):
    _mini_root(tmp_path, traffic_scenario="tcp")
    with pytest.raises(ManifestError):
        manifest.load_cell("cell-a", root=tmp_path)
    (tmp_path / "bench/traffic/mix-a.json").unlink()
    with pytest.raises(ManifestError):
        manifest.load_cell("cell-a", root=tmp_path)


def test_unknown_scenario_refused(tmp_path):
    _mini_root(tmp_path)
    (tmp_path / "bench/configs/cfg-a.json").write_text(
        json.dumps({"name": "cfg-a", "scenario": "tcp"})
    )
    (tmp_path / "bench/traffic/mix-a.json").write_text(json.dumps({"scenario": "tcp"}))
    with pytest.raises(ManifestError):
        manifest.load_cell("cell-a", root=tmp_path)
    with pytest.raises(ManifestError):
        manifest.scenario("no-such-scenario")
    with pytest.raises(ManifestError):
        manifest.scenario(None)


#: what the harness asks of every scenario file
SCENARIO_API = ("KNOB_GROUPS", "request_fields", "offered_packets",
                "guarantee_numbers", "lane_reference", "lane_gaps")


@pytest.mark.parametrize(
    "name", sorted(p.stem for p in (BENCH / "scenarios").glob("*.py"))
)
def test_every_scenario_file_provides_the_harness_api(name):
    mod = manifest.scenario(name)
    for attr in SCENARIO_API:
        assert hasattr(mod, attr), (name, attr)
    assert mod is manifest.scenario(name)


def test_duplicate_metric_names_refused(tmp_path):
    man = _mini_root(tmp_path)
    man["per_layer"].append(dict(man["per_layer"][0]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(ManifestError):
        manifest.load_manifest(tmp_path)


def test_peaks_row_of_the_v5e():
    row = trace.device_peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(trace.UnknownDevice):
        trace.device_peaks("TPU v99")
