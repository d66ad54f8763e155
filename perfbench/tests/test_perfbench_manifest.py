"""BENCHMARK.json resolves every cell's files by name; runs without a TPU
or without the program fail."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = manifest.load(ROOT)


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"]
              for k in ("name", "config", "traffic")]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert BENCH["paths"] == ["perfbench"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_its_files(cell):
    c = manifest.cell(cell, ROOT)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert c.traffic["entry"] in ("simulate", "sweep_grid")
    assert c.limits
    assert {m["name"] for m in c.end_to_end} == {"sim_years_per_s",
                                                 "setup_s"}
    for m in c.per_layer:
        assert callable(manifest.reader(m["name"], ROOT).read)
    configs = {x["name"]: x for x in BENCH["configs"]}
    published = c.config["published"]
    for key in configs[c.config["name"]]["reduced"]:
        assert c.config["workload"][key] != published[key]


def test_a_cell_added_as_files_is_found_without_an_edit(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    pb = tmp_path / "perfbench"
    cfg = json.loads((pb / "configs" / "surf.json").read_text())
    cfg["name"] = "surf_half"
    cfg["workload"]["n_hosts"] = 138
    (pb / "configs" / "surf_half.json").write_text(json.dumps(cfg))
    (pb / "traffic" / "two_regions.json").write_text(json.dumps(
        {"entry": "sweep_grid", "axes": [{"trace": 2}]}))
    (pb / "limits" / "half-two.json").write_text(json.dumps(
        {"count_gap": 0, "delay_rel_gap": 1e-4, "facility_rel_gap": 1e-4}))
    (pb / "metrics" / "task_count.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "surf_half", "source": "x",
                             "file": "perfbench/configs/surf_half.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "half-two", "config": "surf_half",
                               "traffic": "two_regions", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "task_count", "unit": "tasks",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "setup_s",
                               "workloads": ["half-two"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = manifest.cell("half-two", tmp_path)
    assert cell.config["workload"]["n_hosts"] == 138
    assert cell.traffic["axes"] == [{"trace": 2}]
    assert "task_count" in [m["name"] for m in cell.per_layer]
    assert manifest.reader("task_count", tmp_path).read(None) == 42.0
    assert "task_count" not in [m["name"] for m in
                                manifest.cell("surf-composed",
                                              tmp_path).per_layer]


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "surf-composed",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_fails_naming_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_run_without_the_program_fails(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
