"""The Marconi 100 deployment's GPU-bound first fit, and the per-host sums
as a layer of their own.

At a size the CPU can hold: the Marconi cell cut to 8 hosts and 1 day,
where the GPUs bind first fit, agrees with the plain reference; the cell
resolves from BENCHMARK.json; the compiled program names both per-host
sums `stage_per_host_sum`, and the scope changes no instruction; the
sums' work count and their two readers read as documented, in the cells
they apply to.
"""
import copy
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import (compare, fresh_scopes, generator, manifest,  # noqa: E402
                       per_host_sum_work, program_view, reference, scopes,
                       system, trace)
from perfbench.record_trace import tiny_cell  # noqa: E402

SEED = 2**31 + 515
SCOPE = "stage_per_host_sum"
READERS = ("per_host_sum_ms", "per_host_sum_roofline_pct")


@pytest.fixture(scope="module")
def marconi_tiny():
    """One call of the Marconi cell's program at 8 hosts and 1 day."""
    import jax
    cell = tiny_cell("marconi-composed")
    study = generator.study(cell.config, cell.traffic, SEED)
    program = system.build(cell.config, cell.traffic, study)
    out = jax.block_until_ready(program.fn(*program.args))
    return cell, study, program, out


def test_gpus_bind_first_fit_and_the_program_agrees_with_the_reference(
        marconi_tiny):
    cell, study, program, out = marconi_tiny
    dep = study.deployment
    assert dep.n_hosts == 8 and dep.gpus_per_host == 4
    prog = system.outputs(program, out, study)
    nums = compare.numbers(prog, *compare.reference_for(cell.config, study),
                           dep.n_steps * dep.dt_h)
    correct, checks = compare.verdict(nums, cell.limits)
    assert correct, checks
    # placements fail inside the loop: candidates that fit no host's GPUs
    iters = float(out.metrics.first_fit_iters)
    placed = float(out.metrics.first_fit_placed)
    assert 0 < placed < iters
    # with the same tasks asking for no GPU, the reference starts more
    no_gpus = dep._replace(gpus=dep.gpus * 0, gpu_util=dep.gpu_util * 0)
    assert _n_started(cell.config["sim"], no_gpus) > prog["n_started"][0]


def _n_started(sim, dep):
    dem = reference.demand(sim, dep)
    return reference.task_summary(dep, dem["first_start"],
                                  dem["finish"])["n_started"]


def test_it_power_has_a_gpu_term(marconi_tiny):
    cell, study, program, out = marconi_tiny
    dep = study.deployment
    it_kwh = float(system.outputs(program, out, study)["it_energy"][0])
    sim = cell.config["sim"]
    full = reference.demand(sim, dep)["it_kw"].sum() * dep.dt_h
    no_gpu = copy.deepcopy(sim)
    no_gpu["gpu_power"].update(idle_w=0.0, max_w=0.0)
    cpu_only = reference.demand(no_gpu, dep)["it_kw"].sum() * dep.dt_h
    assert it_kwh == pytest.approx(full, rel=1e-4)
    # 4 GPUs of 40-300 W beside a 100-300 W CPU: well over a third more
    assert it_kwh > 1.3 * cpu_only


def test_the_marconi_cell_resolves_from_the_benchmark():
    name = "marconi-composed"
    cell = manifest.cell(name, ROOT)
    assert cell.chips == 1
    assert cell.config["name"] == "marconi"
    assert cell.traffic == json.loads(
        (ROOT / "perfbench" / "traffic" / "single.json").read_text())
    assert cell.limits == json.loads(
        (ROOT / "perfbench" / "limits" / f"{name}.json").read_text())
    w = cell.config["workload"]
    assert (w["n_hosts"], w["cores_per_host"], w["gpus_per_host"]) == \
        (972, 48, 4)
    assert w["horizon_days"] != cell.config["published"]["horizon_days"]


@pytest.mark.parametrize("cell, readers", [
    ("surf-composed", list(READERS)),
    ("marconi-composed", list(READERS)),
    ("surf-battery-study", ["per_host_sum_ms"])])
def test_the_per_host_sum_metrics_apply_to_their_cells(cell, readers):
    per_layer = manifest.cell(cell, ROOT).per_layer
    assert [m["name"] for m in per_layer if m["name"] in READERS] == readers


def _compiled_text(cell, scoped: bool) -> str:
    study = generator.study(cell.config, cell.traffic, SEED)
    if scoped:
        with trace.named_scopes():
            program = system.build(cell.config, cell.traffic, study)
            return program.fn.lower(*program.args).compile().as_text()
    program = system.build(cell.config, cell.traffic, study)
    return program.fn.lower(*program.args).compile().as_text()


@pytest.fixture
def no_compile_cache():
    """The persistent compile cache off: its key leaves out the metadata,
    so it would hand a scoped compile the unscoped program of another test
    (or the other way round)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_both_per_host_sums_are_scoped_and_the_scope_is_metadata_only(
        no_compile_cache):
    cell = tiny_cell("surf-composed")
    scoped = _compiled_text(cell, True)
    paths = set(re.findall(r'op_name="([^"]*)"', scoped))
    for site in ("stage_scheduler.free_capacity", "stage_it_power"):
        assert any(f"/{site}/{SCOPE}/" in p for p in paths), site
    assert fresh_scopes.instructions(scoped) == \
        fresh_scopes.instructions(_compiled_text(cell, False))


def test_per_host_sum_work_matches_a_hand_count():
    # 10 tasks, 3 hosts, 2 steps: a step reads 10 x (4 + 6) columns of 4 B
    # and writes two [3, 2] f32 results; 2 adds in free capacity, 2
    # multiplies and 2 adds in utilization, per task
    ops, nbytes = per_host_sum_work.work(10, 3, 2)
    assert nbytes == 2 * (10 * 10 * 4 + 2 * 3 * 2 * 4)
    assert ops == 2 * 10 * 6


@pytest.fixture
def no_view(monkeypatch):
    """A process with no view yet, whose command line names no cell."""
    monkeypatch.setattr(sys, "argv", ["pytest"])
    monkeypatch.setattr(program_view, "_VIEW", None)


def _run(spec, n_scenarios=1, n_steps=1344):
    """A traced run of one device whose operations, given as (path, ms) or
    (instruction, path, ms), run one after the other."""
    ops, t = [], 0
    for i, op in enumerate(spec):
        name, path, ms = op if len(op) == 3 else (f"fusion.{i}", *op)
        ops.append(trace.Op(name, path, t, int(ms * 1e6)))
        t += int(ms * 1e6)
    busy = trace._union_ns((o.start, o.start + o.dur) for o in ops)
    dev = trace.Device("/device:TPU:0", ops, busy, (0, max(t, 1)))
    return manifest.RunData(trace.Trace([dev], []), 0.1, None, "TPU v5 lite",
                            n_scenarios, n_steps)


BODY = "jit(run)/megakernel.demand/while/body/closed_call/"
SUMS = [(BODY + "stage_scheduler/stage_scheduler.free_capacity/"
         f"{SCOPE}/dot_general", 73.0),
        (BODY + f"stage_it_power/{SCOPE}/dot_general", 73.0),
        (BODY + "stage_progress/mul", 900.0)]


def test_the_readers_read_the_scope_and_the_roofline(no_view, monkeypatch):
    run = _run(SUMS)
    assert manifest.reader("per_host_sum_ms", ROOT).read(run) == \
        pytest.approx(146.0)
    roofline = manifest.reader("per_host_sum_roofline_pct", ROOT)
    assert roofline.read(run) is None         # no cell on the command line
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "surf-composed",
                                      "--seed", str(SEED)])
    config = manifest.cell("surf-composed", ROOT).config
    n_tasks = generator.deployment(config, SEED).arrival.shape[0]
    assert per_host_sum_work.command_line_sizes() == (n_tasks, 277)
    nbytes = 1344 * (n_tasks * 40 + 277 * 16)
    want = 100 * nbytes / 819e9 / 0.146
    assert roofline.read(run) == pytest.approx(want)
    assert 3.5 < want < 5.0                   # SURF's 93,587 tasks
    # a grid hoists one demand scan under all its scenarios: no count
    assert roofline.read(_run(SUMS, n_scenarios=632)) is None


def test_a_program_without_the_scope_reads_nothing(no_view):
    older = _run([(BODY + "stage_scheduler/stage_scheduler.free_capacity/"
                   "dot_general", 73.0)])
    for name in READERS:
        assert manifest.reader(name, ROOT).read(older) is None, name
        assert manifest.reader(name, ROOT).read(
            manifest.RunData(None, 0.1, None, "TPU v5 lite", 1, 96)) is None


@pytest.mark.parametrize("ran_hosts, reads", [(8, True), (9, False)])
def test_a_cached_older_program_still_reads_the_scope(
        no_view, no_compile_cache, monkeypatch, ran_hosts, reads):
    """The compile cache may hand the run an executable compiled from a
    program without the scope (its key leaves the metadata out): the
    trace's operations then carry the older paths, and the reader names
    them again by a fresh compile of the command line's program, where
    that compile is the module that ran, metadata aside (8 hosts), and
    reads nothing where it is not (the run compiled 9)."""
    tiny = tiny_cell("surf-composed")
    monkeypatch.setattr(manifest, "cell", lambda name, *a, **k: tiny)
    monkeypatch.setattr(fresh_scopes, "_TEXTS", {})
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "surf-composed",
                                      "--seed", str(SEED)])
    ran = _compiled_text(tiny_cell("surf-composed", hosts=ran_hosts), False)
    monkeypatch.setattr(program_view, "_VIEW",
                        program_view.View(ran, None, None, None, None))
    _, names = scopes.op_names(_compiled_text(tiny, True))
    ops, under = [], 0
    for name, path in sorted(names.items()):
        if not path:
            continue
        ops.append((name, path.replace(f"/{SCOPE}", ""), 1.0))
        under += f"/{SCOPE}/" in path
    assert under > 0
    assert manifest.reader("per_host_sum_ms", ROOT).read(_run(ops)) == \
        (pytest.approx(float(under)) if reads else None)
