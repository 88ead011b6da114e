"""BENCHMARK.json keeps the benchmark's rules, and the checks catch a
manifest that breaks them."""
import copy
import json

import pytest

from bench import harness, manifest

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def bench_json():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_benchmark_json_holds(bench_json):
    assert manifest.problems(bench_json, ROOT) == []


def test_every_cell_resolves(bench_json):
    for w in bench_json["workloads"]:
        cell = harness.resolve(bench_json, w["name"])
        assert cell.driver_path.is_file()
        assert cell.config["name"] == w["config"]
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def _break(bench_json, how):
    m = copy.deepcopy(bench_json)
    if how == "name":
        m["per_layer"][0]["name"] = "bad name"
    elif how == "unit":
        m["end_to_end"][0]["unit"] = "ms per upload"
    elif how == "long_unit":
        m["end_to_end"][0]["unit"] = "x" * 17
    elif how == "moves":
        # a metric of every cell moving one reported in one cell only
        m["per_layer"][-1]["moves"] = "round_s"
    elif how == "driver":
        m["workloads"][0]["traffic"] = "no_such_traffic"
    elif how == "config":
        m["configs"][0]["file"] = "bench/configs/missing.json"
    elif how == "reader":
        m["per_layer"].append(dict(m["per_layer"][0], name="no_reader"))
    elif how == "extra_key":
        m["end_to_end"][0]["why"] = "not allowed"
    elif how == "bound":
        m["end_to_end"][0]["bound"] = 0.5
    elif how == "pair":
        m["workloads"].append(dict(m["workloads"][0], name="twin"))
    return m


@pytest.mark.parametrize("how", [
    "name", "unit", "long_unit", "moves", "driver", "config", "reader",
    "extra_key", "bound", "pair"])
def test_broken_manifest_is_caught(bench_json, how):
    assert manifest.problems(_break(bench_json, how), ROOT)
