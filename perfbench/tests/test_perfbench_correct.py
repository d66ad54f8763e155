"""`correct` separates a sound program from its control and from broken
programs, at a size the CPU can hold.

The control is the plain reference computed in bfloat16 (the nearest
precision below the float32 the configurations state), put in the
program's place.  The faults are planted under the timed path and the
rest of a run is driven as on the chip: a demand step that returns its
state unchanged, half of the task table left out, and an answer altered
where it is produced.  (The cells' grids place independent scenarios on
chips with no exchange between them, so there is no exchange to leave
out.)
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import control, system  # noqa: E402
from perfbench.record_trace import tiny_cell  # noqa: E402
from perfbench.run import run_cell  # noqa: E402

CELLS = ("surf-composed", "marconi-composed", "surf-battery-study")
SEED = 2**31 + 101


def _run(cell, build=None):
    return run_cell(tiny_cell(cell), SEED, 0.05, False, need_tpu=False,
                    build=build)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_control_is_not(cell):
    got = {side: nums for _, side, nums in control.readings(
        tiny_cell(cell), [SEED], [SEED], need_tpu=False)}
    limits = tiny_cell(cell).limits
    assert all(got["program"][k] <= limits[k] for k in got["program"])
    assert any(got["control"][k] > limits[k] for k in got["control"])


def _state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from repro.core import engine
    monkeypatch.setattr(
        engine, "_build_demand_step",
        lambda cfg, dyn: lambda state, xs: (state,
                                            {"it_kw": jnp.float32(0.0)}))


def _half_the_tasks(config, traffic, study):
    import jax.numpy as jnp
    program = system.build(config, traffic, study)
    tasks = program.args[0]
    arrival = tasks.arrival.at[1::2].set(jnp.inf)
    tasks = tasks._replace(arrival=arrival, status=jnp.where(
        jnp.isfinite(arrival), tasks.status, 3).astype(tasks.status.dtype))
    return program._replace(args=(tasks,) + program.args[1:])


def _altered_answer(config, traffic, study):
    import jax
    import jax.numpy as jnp
    program = system.build(config, traffic, study)
    inner = program.fn

    def fn(*args):
        out = inner(*args)
        if program.entry == "simulate":
            finish = out.tasks.finish
            first = jnp.argmax(jnp.isfinite(finish))
            return out._replace(tasks=out.tasks._replace(
                finish=finish.at[first].add(0.25)))
        return out._replace(grid_energy_kwh=out.grid_energy_kwh.at[
            (0,) * out.grid_energy_kwh.ndim].multiply(1.001))
    return program._replace(fn=jax.jit(fn))


@pytest.mark.parametrize("cell", ["surf-composed", "surf-battery-study"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_tasks",
                                   "altered_answer"])
def test_a_broken_program_is_not_correct(cell, fault, monkeypatch):
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
        build = None
    else:
        build = {"half_the_tasks": _half_the_tasks,
                 "altered_answer": _altered_answer}[fault]
    result = _run(cell, build)
    assert result["correct"] is False
    assert any(v > lim for v, lim in result["checks"].values())
