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


@pytest.mark.parametrize("mesh,chips,ok", [
    (None, 1, True),
    ({"model": 4}, 4, True),
    ({"data": 2, "model": 2}, 4, True),
    ({"model": 4}, 1, False),
    ({"model": 3}, 4, False),
    ({"chips": 4}, 4, False),
    ({}, 4, False),
    ({"model": 4.0}, 4, False),
])
def test_mesh_must_cover_the_chips(mesh, chips, ok):
    config = {} if mesh is None else {"mesh": mesh}
    assert (manifest.mesh_problems(config, chips) == []) == ok


def test_a_mesh_off_the_cells_chips_is_caught(bench_json, tmp_path):
    m = copy.deepcopy(bench_json)
    config = harness.load_json(ROOT / m["configs"][0]["file"])
    config["mesh"] = {"model": 4}
    path = tmp_path / "with_mesh.json"
    path.write_text(json.dumps(config))
    m["configs"][0]["file"] = str(path)   # an absolute path stays as it is
    cells = [w for w in m["workloads"] if w["config"] == config["name"]]
    found = manifest.problems(m, ROOT)
    assert cells and all(any(w["name"] in p and "mesh" in p for p in found)
                         for w in cells)
    for w in cells:
        w["chips"] = 4
    assert manifest.problems(m, ROOT) == []


@pytest.mark.parametrize("driver,ok", [
    ("store_writer", True), ("http_closed", True), ("http_open", False)])
def test_only_drivers_that_record_rounds_take_bf16(driver, ok):
    # bf16 rounds are checked as each ends, which needs the round's
    # uploads as it ends
    mod = harness.load_module(harness.BENCH / "drivers" / f"{driver}.py")
    assert (manifest.check_problems(
        {"payload": {"kind": "bfloat16"}}, mod) == []) == ok
    assert manifest.check_problems({"payload": {"kind": "float32"}},
                                   mod) == []


def test_a_bf16_config_on_the_open_loop_is_caught(bench_json, tmp_path):
    m = copy.deepcopy(bench_json)
    entry = next(c for c in m["configs"] if c["name"] == "xdev_cnn46_int8")
    config = harness.load_json(ROOT / entry["file"])
    config["payload"] = {"kind": "bfloat16"}
    path = tmp_path / "bf16.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)   # an absolute path stays as it is
    found = manifest.problems(m, ROOT)
    assert len(found) == 1 and "http_poisson" in found[0] \
        and "http_open" in found[0], found
