"""Find a cell's files by the names in BENCHMARK.json.

A cell (an entry of `workloads`) names its configuration and its traffic;
each per-layer metric is a reader of its own.  Nothing here lists them:

* configuration  the `file` of its entry in `configs`;
* traffic        perfbench/traffic/<traffic>.json;
* limits         perfbench/limits/<cell>.json, the limit of each number
                 that `compare` holds the cell to;
* metric         perfbench/metrics/<metric>.py, whose `read(run)` returns
                 the number or None when the run has nothing to read.

So a later change adds a cell by adding files and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import NamedTuple, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # metric entries the cell reports with --trace 0
    per_layer: list       # ... and with --trace 1


class RunData(NamedTuple):
    """What a traced run hands the per-layer readers."""
    trace: object             # trace.Trace, or None
    compile_s: float          # backend compile seconds during set-up
    peak_bytes: Optional[int]  # peak_bytes_in_use, fullest device
    device_kind: str
    n_scenarios: int          # per call, over all devices
    n_steps: int


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT, manifest: dict | None = None) -> Cell:
    manifest = manifest or load(root)
    work = {w["name"]: w for w in manifest["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    return assemble(name, configs[w["config"]]["file"], w["traffic"],
                    int(w["chips"]), manifest, root)


def assemble(name: str, config_file: str, traffic: str, chips: int,
             manifest: dict, root: Path = ROOT) -> Cell:
    """A cell from its configuration file, traffic name and limits file
    (perfbench/limits/<name>.json), with the metrics that apply to it."""
    config = _read_json(root / config_file)
    traffic_spec = _read_json(root / "perfbench" / "traffic"
                              / f"{traffic}.json")
    limits = _read_json(root / "perfbench" / "limits" / f"{name}.json")
    return Cell(name, chips, config, traffic_spec, limits,
                [m for m in manifest["end_to_end"] if _applies(m, name)],
                [m for m in manifest["per_layer"] if _applies(m, name)])


def reader(metric: str, root: Path = ROOT):
    """The module perfbench/metrics/<metric>.py."""
    path = root / "perfbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
