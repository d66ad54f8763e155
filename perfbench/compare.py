"""The comparison that decides `correct`: program against plain reference.

Numbers, each held to a limit of its own (perfbench/limits/<cell>.json):

* start_mismatch  tasks whose start time differs (one-scenario cells,
                  which return the task table): exact, limit 0;
* finish_gap_h    widest gap between a task's finish time in the program
                  and in the reference, hours (a task finished in one and
                  not the other counts the whole horizon);
* count_gap       widest gap over scenarios in done plus started tasks:
                  exact, limit 0;
* delay_rel_gap   widest relative gap over scenarios in the mean task
                  delay and the mean start delay;
* facility_rel_gap widest relative gap over scenarios in IT, cooling, PV,
                  export, grid and discharged energy, operational carbon,
                  energy and demand cost (and the final battery charge
                  where the call returns it).  Each scenario is held to the
                  nearest of the outcomes float32 allows
                  (`reference.facility_outcomes`).

A relative gap is |program - reference| / max(|reference|, 1) in the
quantity's unit (kWh, kg, currency, hours): every such total is hundreds
or more, so the floor only matters for a quantity that is zero in both.
"""
from __future__ import annotations

import numpy as np

FACILITY_KEYS = ("it_energy", "cooling_energy", "pv_energy", "export_energy",
                 "grid_energy", "batt_discharged", "op_carbon", "energy_cost",
                 "demand_cost", "soc_final")
DELAY_KEYS = ("mean_delay_h", "mean_start_delay_h")
ORDER = ("start_mismatch", "finish_gap_h", "count_gap", "delay_rel_gap",
         "facility_rel_gap")


def _rel(p, r):
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    return np.abs(p - r) / np.maximum(np.abs(r), 1.0)


def numbers(prog: dict, ref_demand: dict, ref_tasks: dict,
            ref_facility: dict, owner: np.ndarray, horizon_h: float) -> dict:
    """The compared numbers of one call.

    `prog` is `system.outputs`; `ref_demand` is `reference.demand`,
    `ref_tasks` its `task_summary`; `ref_facility`/`owner` are
    `reference.facility_outcomes` over the same scenarios."""
    out = {}
    if "first_start" in prog:
        a, b = prog["first_start"], ref_demand["first_start"]
        out["start_mismatch"] = int(np.sum(~((a == b) | (np.isinf(a)
                                                         & np.isinf(b)))))
        a, b = prog["finish"], ref_demand["finish"]
        both = np.isfinite(a) & np.isfinite(b)
        one = np.isfinite(a) != np.isfinite(b)
        gap = np.abs(a[both] - b[both]).max(initial=0.0)
        out["finish_gap_h"] = float(horizon_h if one.any() else gap)
    out["count_gap"] = int(np.max(
        np.abs(prog["n_done"] - ref_tasks["n_done"])
        + np.abs(prog["n_started"] - ref_tasks["n_started"])))
    out["delay_rel_gap"] = float(max(
        np.max(_rel(prog[k], ref_tasks[k])) for k in DELAY_KEYS))
    n = len(prog["it_energy"])
    per_row = np.zeros(len(owner))
    for k in FACILITY_KEYS:
        if k in prog:
            per_row = np.maximum(per_row,
                                 _rel(prog[k][owner], ref_facility[k]))
    best = np.full(n, np.inf)
    np.minimum.at(best, owner, per_row)
    out["facility_rel_gap"] = float(best.max())
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]}) in a fixed order; a number with
    no limit, or a limit with no number, is a fault of the benchmark."""
    if set(nums) - set(limits):
        raise KeyError(f"no limit for {sorted(set(nums) - set(limits))}")
    pairs = {k: [nums[k], limits[k]] for k in ORDER if k in nums}
    ok = all(np.isfinite(v) and v <= lim for v, lim in pairs.values())
    return bool(ok), pairs


def reference_for(config: dict, study):
    """Run the reference over a study: (demand, task summary, facility
    outcomes, owner)."""
    from . import reference
    sim = config["sim"]
    dem = reference.demand(sim, study.deployment)
    tasks = reference.task_summary(study.deployment, dem["first_start"],
                                   dem["finish"])
    tasks = {k: np.full(study.n_scenarios, v, np.float64)
             for k, v in tasks.items()}
    fac, owner = reference.facility_outcomes(
        sim, dem["it_kw"], study.ci, study.shared, study.dyn)
    return dem, tasks, fac, owner


def control(config: dict, study) -> dict:
    """The control that the comparison has to refuse: the reference in
    bfloat16, the nearest precision below the float32 that the
    configurations state, put in the program's place with the keys that
    `system.outputs` returns."""
    from . import reference
    sim = config["sim"]
    dem = reference.demand(sim, study.deployment, "bfloat16")
    out = {k: np.full(study.n_scenarios, v, np.float64)
           for k, v in reference.task_summary(
               study.deployment, dem["first_start"], dem["finish"]).items()}
    out.update(reference.facility(sim, dem["it_kw"], study.ci, study.shared,
                                  study.dyn, "bfloat16"))
    if study.axes:
        out.pop("soc_final")
    else:
        out["first_start"], out["finish"] = dem["first_start"], dem["finish"]
    return out
