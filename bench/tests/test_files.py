"""Every file the benchmark names is there and agrees with BENCHMARK.json."""

import json
import re

import pytest

from bench import check, harness

BENCHMARK = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: end-to-end metrics the harness measures itself
MEASURED = {"images_per_s", "step_hbm_gib", "setup_s"}


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"][1] == "bench/run.py"
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


@pytest.mark.parametrize("cfg", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    data = harness.load_json(harness.ROOT / cfg["file"])
    assert data["name"] == cfg["name"] and NAME.match(cfg["name"])
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    model = harness.load_module(
        harness.BENCH / "configs" / f"{data['model']}.py", "m")
    for fn in ("conv_layers", "linear", "init", "forward"):
        assert callable(getattr(model, fn))


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda c: c["name"])
def test_workload_files(cell):
    workload, config, _ = harness.load_cell(cell["name"])
    for key in ("name", "config", "traffic", "chips", "why"):
        assert workload[key] == cell[key], key
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert len(cell["why"]) <= 200
    assert config["name"] == cell["config"]
    assert set(workload["limits"]) <= set(check.NAMES)
    assert 0 < min(workload["limits"].values())
    assert workload["batch"] % harness.ref_block(workload["batch"]) == 0


def test_pairs_are_unique():
    pairs = [(c["config"], c["traffic"]) for c in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_readers(metric):
    reader = harness.load_module(
        harness.BENCH / "metrics" / f"{metric['name']}.py", "r")
    assert callable(reader.read)
    names = {c["name"] for c in BENCHMARK["workloads"]}
    assert set(metric.get("workloads", names)) <= names
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


def test_end_to_end_metrics_are_measured():
    assert {m["name"] for m in BENCHMARK["end_to_end"]} <= MEASURED
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_peaks_name_their_source():
    for kind, peak in harness.load_json(harness.BENCH / "peaks.json").items():
        assert peak["source"] and peak["bf16_flops_per_s"] > 0
        assert peak["hbm_bytes_per_s"] > 0


def test_seed31_takes_large_seeds():
    s = [harness.seed31(x) for x in (0, 2**31 + 5, 2**33 + 5, -1, 10**12)]
    assert len(set(s)) == len(s)
    assert all(0 <= x < 2**31 for x in s)
    assert harness.seed31(2**33 + 5) == harness.seed31(2**33 + 5)
