"""The system under test: one compiled call of the program per cell.

`build` turns a configuration and a study into the program's own objects
(`SimConfig`, task and host tables) and one jitted entry that the window
drives: `simulate` for a one-scenario study, `sweep_grid` (with
`jit=False`, under one outer `jax.jit`) for a grid.  The task and host
tables are arguments of the compiled program, not constants closed over,
so the program and its compile-cache key do not depend on `--seed`.

`outputs` reads what a call returned into the plain numbers that
`compare` holds against the reference.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import numpy as np


class Program(NamedTuple):
    fn: Callable            # the jitted entry
    args: tuple             # its arguments (device arrays)
    cfg: object             # repro SimConfig
    entry: str              # "simulate" | "sweep_grid"


def sim_config(config: dict):
    """The configuration's `sim` block as the program's SimConfig."""
    from repro.core import SimConfig
    from repro.core.config import EmbodiedConfig

    fields = {f.name: f for f in dataclasses.fields(SimConfig)}
    kw = {}
    for key, val in config["sim"].items():
        default = fields[key].default
        if dataclasses.is_dataclass(default):
            kw[key] = dataclasses.replace(default, **val)
        else:
            kw[key] = val
    kw["embodied"] = EmbodiedConfig(
        host_kg=config["workload"]["host_embodied_kg"])
    from . import generator
    kw["n_steps"] = generator.n_steps(config)
    return SimConfig(**kw)


def arguments(study) -> tuple:
    """The program's arguments for one study: task and host tables, the
    swept values (or the one carbon trace) and the shared series.  Studies
    of one cell have the same shapes whatever the seed, so one compiled
    entry serves them all."""
    import jax.numpy as jnp
    from repro.core import make_host_table, make_task_table

    dep = study.deployment
    tasks = make_task_table(dep.arrival, dep.duration, dep.cores, dep.gpus,
                            dep.cpu_util, dep.gpu_util)
    hosts = make_host_table(dep.n_hosts, dep.cores_per_host,
                            dep.gpus_per_host)
    shared = {k: jnp.asarray(v) for k, v in study.shared.items()}
    if not study.axes:
        return tasks, hosts, jnp.asarray(study.ci[0]), shared
    return tasks, hosts, tuple(jnp.asarray(v) for _, _, v in study.axes), \
        shared


def build(config: dict, traffic: dict, study) -> Program:
    import jax
    from repro.core import dyn_axis, simulate, sweep_grid, trace_axis

    cfg = sim_config(config)
    if traffic["entry"] == "simulate":
        def run(tasks, hosts, ci, shared):
            final, _ = simulate(tasks, hosts, ci, cfg, dyn=dict(shared))
            return final
    elif traffic["entry"] == "sweep_grid":
        kinds = [(kind, name) for kind, name, _ in study.axes]

        def run(tasks, hosts, values, shared):
            axes = [trace_axis(v) if kind == "trace"
                    else dyn_axis(**{name: v})
                    for (kind, name), v in zip(kinds, values)]
            return sweep_grid(tasks, hosts, cfg, axes, dyn=dict(shared),
                              jit=False)
    else:
        raise ValueError(f"unknown entry {traffic['entry']!r}")
    return Program(jax.jit(run), arguments(study), cfg, traffic["entry"])


def outputs(program: Program, out, study) -> dict:
    """What one call returned, as f64 host arrays over the N scenarios.

    Keys shared by both entries: n_done, n_started, mean_delay_h,
    mean_start_delay_h and the facility totals (it_energy, cooling_energy,
    pv_energy, batt_discharged, grid_energy, export_energy, op_carbon,
    energy_cost, demand_cost).  `simulate` adds the per-task first_start
    and finish, and soc_final."""
    import jax
    out = jax.device_get(out)
    f64 = lambda x: np.asarray(x, np.float64).reshape(-1)
    if program.entry == "simulate":
        from . import reference
        m, tasks = out.metrics, out.tasks
        first_start, finish = f64(tasks.first_start), f64(tasks.finish)
        res = reference.task_summary(study.deployment, first_start, finish)
        res = {k: np.asarray([v], np.float64) for k, v in res.items()}
        dchg = program.cfg.pricing.demand_charge_per_kw
        res.update(
            first_start=first_start, finish=finish,
            it_energy=f64(m.it_energy), cooling_energy=f64(m.cooling_energy),
            pv_energy=f64(m.pv_energy),
            batt_discharged=f64(m.batt_discharged),
            grid_energy=f64(m.grid_energy),
            export_energy=f64(m.export_energy), op_carbon=f64(m.op_carbon),
            energy_cost=f64(m.energy_cost),
            demand_cost=f64(m.demand_cost) + f64(m.window_peak_kw) * dchg,
            soc_final=f64(out.battery.charge))
        return res
    return {
        "n_done": f64(out.n_done), "n_started": f64(out.n_started),
        "mean_delay_h": f64(out.mean_delay_h),
        "mean_start_delay_h": f64(out.mean_start_delay_h),
        "it_energy": f64(out.it_energy_kwh),
        "cooling_energy": f64(out.cooling_energy_kwh),
        "pv_energy": f64(out.pv_energy_kwh),
        "batt_discharged": f64(out.batt_discharged_kwh),
        "grid_energy": f64(out.grid_energy_kwh),
        "export_energy": f64(out.grid_export_kwh),
        "op_carbon": f64(out.op_carbon_kg),
        "energy_cost": f64(out.energy_cost),
        "demand_cost": f64(out.demand_cost),
    }
