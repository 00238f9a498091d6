"""BENCHMARK.json keeps to its shape, and every entry resolves to its files
by name alone."""
import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_shape():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_have_only_their_keys_and_valid_names():
    cfg_keys = {"name", "source", "file", "reduced", "why"}
    wl_keys = {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert set(c) == cfg_keys and NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == wl_keys and NAME.match(w["name"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(wl):
    conf = spec.load_config(BENCH, wl["config"])
    entry = spec.config_entry(BENCH, wl["config"])
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    mix = spec.load_traffic(wl["traffic"])
    assert hasattr(spec.driver_module(mix["kind"]), "Driver")
    assert spec.load_limits(wl["name"])
    e2e = spec.cell_metrics(BENCH, wl["name"], trace=False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert spec.cell_metrics(BENCH, wl["name"], trace=True)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves(m):
    assert callable(spec.metric_reader(m["name"]).read)
    moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
    for w in m["workloads"]:
        assert w in moved.get("workloads", [w])
