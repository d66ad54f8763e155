"""The Google Borg deployment: placement at thousands of slots a step.

At a size the CPU can hold: the Borg cell cut to 1,534/64 hosts and 1
day, its 4,096 slots a step kept, runs the placement kernel (interpreted)
and agrees with the plain reference, and its bfloat16 control does not;
the placement bound never binds there, where it binds in a 64-slot SURF
cut; the cell resolves from BENCHMARK.json at Borg's
published widths; `commit_ms` reads the commit's scope in a recorded
device trace.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import (compare, generator, manifest, scopes,  # noqa: E402
                       system, trace)
from perfbench.record_trace import tiny_cell  # noqa: E402

SEED = 2**31 + 1901
NAME = "borg-composed"
SCOPED = ROOT / "perfbench" / "tests" / "surf_tiny_scoped"


def _call(cell, form=None):
    """One call of the cell's program, with the placement form forced
    where `form` is given: (study, program, final state)."""
    import jax
    from repro.core import scheduler
    study = generator.study(cell.config, cell.traffic, SEED)
    with pytest.MonkeyPatch.context() as mp:
        if form is not None:
            mp.setattr(scheduler, "first_fit_form", lambda: form)
        program = system.build(cell.config, cell.traffic, study)
        out = jax.block_until_ready(program.fn(*program.args))
    return study, program, out


@pytest.fixture(scope="module")
def borg_tiny():
    """One call of the Borg cell's program at 24 hosts and 1 day, placed
    by the Pallas kernel (interpret mode on the CPU)."""
    cell = tiny_cell(NAME, hosts=round(1534 / 64))
    return (cell,) + _call(cell, "pallas")


def test_the_kernel_placed_program_agrees_with_the_reference(borg_tiny):
    cell, study, program, out = borg_tiny
    dep = study.deployment
    assert (dep.n_hosts, dep.cores_per_host) == (24, 64)
    assert cell.config["sim"]["scheduler"]["slots_per_step"] == 4096
    nums = compare.numbers(system.outputs(program, out, study),
                           *compare.reference_for(cell.config, study),
                           dep.n_steps * dep.dt_h)
    correct, checks = compare.verdict(nums, cell.limits)
    assert correct, checks
    assert nums["start_mismatch"] == 0 and nums["count_gap"] == 0
    # every candidate of every step placed: the loop's yield is 100%
    iters = float(out.metrics.first_fit_iters)
    assert float(out.metrics.first_fit_placed) == iters > 0


def test_the_bfloat16_control_is_refused(borg_tiny):
    cell, study, _, _ = borg_tiny
    dep = study.deployment
    nums = compare.numbers(compare.control(cell.config, study),
                           *compare.reference_for(cell.config, study),
                           dep.n_steps * dep.dt_h)
    correct, checks = compare.verdict(nums, cell.limits)
    assert not correct, checks


def test_the_slot_bound_binds_on_surf_and_not_on_borg(borg_tiny):
    from repro.core import summarize
    _, _, program, out = borg_tiny
    assert float(out.metrics.slot_bound_steps) == 0.0
    surf = tiny_cell("surf-composed", hosts=277)
    assert surf.config["sim"]["scheduler"]["slots_per_step"] == 64
    study, program, out = _call(surf)
    steps = float(out.metrics.slot_bound_steps)
    assert steps == float(summarize(out, program.cfg).slot_bound_steps)
    assert 0 < steps <= study.deployment.n_steps


def test_the_borg_cell_resolves_from_the_benchmark():
    import dataclasses

    from repro.workloads.synthetic import BORG
    cell = manifest.cell(NAME, ROOT)
    assert cell.chips == 1 and cell.config["name"] == "borg"
    assert cell.traffic == manifest.cell("surf-composed", ROOT).traffic
    assert cell.limits == manifest.cell("surf-composed", ROOT).limits
    w = cell.config["workload"]
    published = dataclasses.asdict(BORG)
    for key, value in w.items():
        if key not in ("horizon_days", "scale"):
            want = published[key]
            assert value == (list(want) if isinstance(want, tuple)
                             else want), key
    assert (w["horizon_days"], w["scale"]) == (7, 1.0)
    assert cell.config["published"]["horizon_days"] == BORG.horizon_days
    assert "commit_ms" in {m["name"] for m in cell.per_layer}


def test_commit_ms_reads_the_commit_scope_of_a_recorded_trace():
    tr = trace.load(f"{SCOPED}.xplane.pb.gz")
    scopes.attribute(tr, scopes.read_text(f"{SCOPED}.hlo.txt.gz"))
    run = manifest.RunData(tr, 0.1, None, "TPU v5 lite", 1, 96)
    dev = tr.devices[0]
    commit = scopes.scope_ns(dev, "stage_scheduler.commit")
    assert 0 < commit < scopes.scope_ns(dev, "stage_scheduler")
    assert manifest.reader("commit_ms", ROOT).read(run) == \
        pytest.approx(commit / 1e6)
    empty = manifest.RunData(trace.Trace([], []), 0.1, None, "TPU v5 lite",
                             1, 96)
    assert manifest.reader("commit_ms", ROOT).read(empty) is None
