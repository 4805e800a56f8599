"""BENCHMARK.json and the files it names, found by name."""
import json
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_benchmark_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for wl in bench["workloads"]:
        assert len(wl["why"]) <= 200 and wl["chips"] in (1, 4)
        cfg = harness.load_named("configs", wl["config"])
        assert cfg["name"] == wl["config"]
        assert configs[wl["config"]]["file"] == (
            f"chipbench/configs/{wl['config']}.json")
        assert cfg["chips"] == wl["chips"]
        traffic = harness.load_named("traffic", wl["traffic"])
        assert callable(harness.load_driver(traffic["driver"]).run)
        limits = harness.load_named("checks", wl["name"])["limits"]
        assert limits and all("limit" in v for v in limits.values())


def test_every_per_layer_metric_has_a_reader(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert callable(harness.load_metric_reader(m["name"]))
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


def test_unknown_names_raise(bench):
    with pytest.raises(KeyError):
        harness.find_workload(bench, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        harness.load_named("traffic", "no-such-mix")
    with pytest.raises(FileNotFoundError):
        harness.load_metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v0")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_applies_selects_metrics_by_cell():
    listed = {"name": "m", "moves": "a", "workloads": ["x"]}
    assert harness.applies(listed, "x", set())
    assert not harness.applies(listed, "y", {"a"})
    assert harness.applies({"name": "setup_s"}, "y", {"setup_s"})
    assert not harness.applies({"name": "m", "moves": "a"}, "y", {"b"})


def test_compare_holds_each_reading_to_its_limit():
    limits = {"a": {"limit": 1.0}, "b": {"limit": 2.0}}
    ok, out = harness.compare({"a": 0.5, "b": 2.0}, limits)
    assert ok and out == {"a": {"value": 0.5, "limit": 1.0},
                          "b": {"value": 2.0, "limit": 2.0}}
    assert not harness.compare({"a": 1.5, "b": 0.0}, limits)[0]
    assert not harness.compare({"a": float("nan"), "b": 0.0}, limits)[0]
    with pytest.raises(KeyError):
        harness.compare({"a": 0.5}, limits)


def test_config_files_state_the_paper_deployment():
    cfg = harness.load_named("configs", "paper-exp1")
    p = cfg["spec"]["problem"]
    assert (p["L"], p["d"], p["T"], p["r"], p["n"]) == (20, 600, 600, 4, 30)
    assert cfg["reduced"] == ["eta", "T_GD"] == sorted(cfg["cuts"],
                                                       reverse=True)
    assert cfg["precision"] == "highest"
    json.dumps(harness.spec_from_config(cfg, T_GD=100).to_dict())
