"""Find a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names configurations, traffic
mixes and per-layer metrics; each lives in a file of its own here, found by
its name alone, so a later cell adds files and never edits the harness:

* ``configs/<file>``            a configuration (named by its ``file`` entry)
* ``references/<name>.py``      the block module a configuration names in
                                ``run.reference``: its widths, init scales,
                                float32 forward and work counts
* ``traffic/<mix>.json``        a traffic mix; its ``kind`` names the driver
                                module ``traffic/<kind>.py``
* ``metrics/<metric>.py``       the reader of one per-layer metric
* ``limits/<workload>.json``    the limits that decide ``correct``
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def _one(entries, name: str, what: str) -> dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise KeyError(f"{what} {name!r}: {len(hits)} entries in BENCHMARK.json")
    return hits[0]


def workload(bench: dict, name: str) -> dict:
    return _one(bench["workloads"], name, "workload")


def config_entry(bench: dict, name: str) -> dict:
    return _one(bench["configs"], name, "config")


def load_config(bench: dict, name: str) -> dict:
    return json.loads((ROOT / config_entry(bench, name)["file"]).read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def load_limits(workload_name: str) -> dict:
    return json.loads((HERE / "limits" / f"{workload_name}.json").read_text())


def reference_module(conf: dict):
    """The module ``references/<name>.py`` that ``conf["run"]["reference"]``
    names."""
    return importlib.import_module(
        f"chipbench.references.{conf['run']['reference']}")


def driver_module(kind: str):
    return importlib.import_module(f"chipbench.traffic.{kind}")


def metric_reader(name: str):
    """The module ``metrics/<name>.py`` (names may hold dots)."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics._{name.replace('.', '_')}", path)
    if mod_spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload_name: str, trace: bool) -> list:
    """The metric entries a cell reports: its end-to-end metrics without a
    trace, its per-layer metrics with one."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload_name in m.get("workloads", [workload_name])]
