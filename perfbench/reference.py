"""Plain reference simulator: the semantics the benchmark holds the program to.

A straightforward sequential NumPy implementation of one simulated
datacenter, written from the engine's documented semantics and importing
nothing of the program.  Per 15-minute step, in this order:

1. scheduling: tasks that have arrived and not started queue in arrival
   order; the first `slots_per_step` of them are tried in turn, each on the
   lowest-numbered host with enough free cores and GPUs (first fit); a task
   that fits nowhere stays queued;
2. progress: every running task does `dt_h` hours of work; one whose
   remaining work is at most that finishes at `t + remaining` and frees its
   host;
3. IT power: per host, utilization = sum(cores x cpu_util) / cores (the same
   for GPUs), through the configured power curves, summed over hosts.

The facility chain then runs over the horizon for every scenario: cooling
(fan/pump overhead + a weather-driven chiller, heat reuse), PV netted
against the load, the carbon-threshold battery with its C-rate, efficiency
and surplus charging, the grid import that results, energy and
billing-window demand charges, and operational carbon.

`precision` is the arithmetic: "float64" is the reference; "bfloat16"
rounds every intermediate to bfloat16, the nearest precision below the
float32 that the configurations state, and serves as the control.
"""
from __future__ import annotations

import numpy as np

PRECISIONS = ("float64", "bfloat16")


def _rounding(precision: str):
    if precision == "float64":
        return lambda x: np.asarray(x, np.float64)
    if precision == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}; pick one of "
                     f"{PRECISIONS}")


def _curve(model: str):
    return {"linear": lambda u: u, "sqrt": np.sqrt,
            "square": lambda u: u * u, "cubic": lambda u: u * u * u}[model]


def demand(sim: dict, dep, precision: str = "float64") -> dict:
    """Scheduling, progress and IT power of one deployment.

    Returns first_start[T] and finish[T] in hours (inf where it never
    happened) and it_kw[S], the IT draw of every step."""
    q = _rounding(precision)
    sched = sim["scheduler"]
    if sched["mode"] != "first_fit" or sched["priority_levels"] != 1:
        raise NotImplementedError("the reference schedules FIFO first fit")
    k = int(sched["slots_per_step"])
    cpu_cfg, gpu_cfg = sim["cpu_power"], sim["gpu_power"]
    cpu_curve, gpu_curve = _curve(cpu_cfg["model"]), _curve(gpu_cfg["model"])
    h, s_n, dt = dep.n_hosts, dep.n_steps, dep.dt_h

    arrival, duration = q(dep.arrival), q(dep.duration)
    cores, gpus = q(dep.cores), q(dep.gpus)
    cpu_load, gpu_load = q(cores * q(dep.cpu_util)), q(gpus * q(dep.gpu_util))
    t_n = arrival.shape[0]
    free_c = np.full(h, float(dep.cores_per_host))
    free_g = np.full(h, float(dep.gpus_per_host))
    host = np.full(t_n, -1, np.int64)
    remaining = duration.copy()
    first_start = np.full(t_n, np.inf)
    finish = np.full(t_n, np.inf)
    it_kw = np.zeros(s_n)
    adv = float(q(dt))
    queue: list[int] = []
    arrived = 0
    running = np.zeros(0, np.int64)
    for s in range(s_n):
        t = float(q(s * dt))
        now_arrived = int(np.searchsorted(arrival, t, side="right"))
        queue.extend(range(arrived, now_arrived))
        arrived = now_arrived
        placed = []
        for j in queue[:k]:
            fits = (free_c >= cores[j]) & (free_g >= gpus[j])
            hj = int(np.argmax(fits))
            if fits[hj]:
                free_c[hj] -= cores[j]
                free_g[hj] -= gpus[j]
                host[j] = hj
                first_start[j] = t
                placed.append(j)
        if placed:
            queue = [j for j in queue[:k] if host[j] < 0] + queue[k:]
            running = np.concatenate([running, np.asarray(placed, np.int64)])
        rem = remaining[running]
        done = rem <= adv
        if done.any():
            d = running[done]
            finish[d] = q(t + rem[done])
            np.add.at(free_c, host[d], cores[d])
            np.add.at(free_g, host[d], gpus[d])
        remaining[running] = q(np.maximum(q(rem - adv), 0.0))
        running = running[~done]
        on = host[running]
        cpu = q(np.bincount(on, weights=cpu_load[running], minlength=h))
        cpu_u = np.clip(q(cpu / dep.cores_per_host), 0.0, 1.0)
        p = q(cpu_cfg["idle_w"] + q((cpu_cfg["max_w"] - cpu_cfg["idle_w"])
                                    * q(cpu_curve(cpu_u))))
        if dep.gpus_per_host > 0:
            gpu = q(np.bincount(on, weights=gpu_load[running], minlength=h))
            gpu_u = np.clip(q(gpu / dep.gpus_per_host), 0.0, 1.0)
            p = q(p + q(q(gpu_cfg["idle_w"] + q(
                (gpu_cfg["max_w"] - gpu_cfg["idle_w"]) * q(gpu_curve(gpu_u))))
                * dep.gpus_per_host))
        it_kw[s] = q(np.sum(q(p / 1000.0)))
    return {"first_start": first_start, "finish": finish, "it_kw": it_kw}


def task_summary(dep, first_start, finish) -> dict:
    """Done and started counts and the mean delays, as a study reports
    them: delay = finish - (arrival + duration) over done tasks, start
    delay = first start - arrival over started tasks."""
    arrival = dep.arrival.astype(np.float64)
    expected = arrival + dep.duration.astype(np.float64)
    t_end = dep.n_steps * dep.dt_h
    done = np.isfinite(finish)
    started = (arrival <= t_end) & np.isfinite(first_start)
    n_done, n_started = int(done.sum()), int(started.sum())
    return {
        "n_done": n_done, "n_started": n_started,
        "mean_delay_h": float(np.sum(np.maximum(finish[done] - expected[done],
                                                0.0)) / max(n_done, 1)),
        "mean_start_delay_h": float(np.sum(first_start[started]
                                           - arrival[started])
                                    / max(n_started, 1)),
    }


def battery_signals(ci: np.ndarray, dt_h: float, window_h: float):
    """(threshold, rising) f64[N, S]: the trailing mean of the carbon
    intensity over the window (expanding before a full window exists), and
    whether the intensity stopped decreasing."""
    ci = np.asarray(ci, np.float64)
    s = ci.shape[-1]
    w = max(int(round(window_h / dt_h)), 1)
    csum = np.concatenate([np.zeros(ci.shape[:-1] + (1,)),
                           np.cumsum(ci, axis=-1)], axis=-1)
    idx = np.arange(s)
    lo = np.maximum(idx + 1 - w, 0)
    threshold = (csum[..., idx + 1] - csum[..., lo]) / (idx + 1 - lo)
    prev = np.concatenate([ci[..., :1], ci[..., :-1]], axis=-1)
    return threshold, ci >= prev


def facility(sim: dict, it_kw, ci, shared: dict, dyn: dict | None = None,
             precision: str = "float64", flip=None) -> dict:
    """The facility chain of N scenarios over S steps.

    it_kw f[S] is shared; ci f32[N, S] and any swept `dyn` value
    (batt_capacity_kwh, pv_capacity_kw: f32[N]) are per scenario.
    `flip` (bool[N, S] or None) inverts the battery's carbon comparison at
    the marked steps: the comparison's outcome where the threshold lies
    within float32 rounding of the intensity.  Returns f64[N] totals."""
    q = _rounding(precision)
    dyn = dyn or {}
    ci = q(ci)
    n, s_n = ci.shape
    dt = float(sim["dt_h"])
    it = q(np.broadcast_to(np.asarray(it_kw, np.float64), (n, s_n)))
    wb = q(shared["wet_bulb_trace"])[None, :]
    price = q(shared["price_trace"])[None, :]
    cf = q(shared["pv_cf_trace"])[None, :]

    cool = sim["cooling"]
    if cool["enabled"]:
        sp, rng = cool["setpoint_c"], max(cool["economizer_range_c"], 1e-6)
        frac = np.clip(q(q(wb - (sp - rng)) / rng), 0.0, 1.0)
        lift = np.maximum(q(q(q(wb + cool["tower_approach_c"])
                              + cool["condenser_lift_c"]) - sp), 1.0)
        cop = np.clip(q(cool["carnot_efficiency"] * (sp + 273.15) / lift),
                      1.0, cool["max_cop"])
        fan = q(cool["fan_pump_overhead"] * it)
        chiller = q(q(frac * it) / cop)
        cooling = q(fan + chiller)
    else:
        cooling = np.zeros_like(it)
    load = q(it + cooling)

    ren = sim["renewables"]
    if ren["enabled"]:
        pv_cap = np.asarray(dyn.get("pv_capacity_kw",
                                    np.full(n, ren["pv_capacity_kw"])),
                            np.float64)[:, None]
        pv = np.maximum(q(q(pv_cap) * cf), 0.0)
        net = np.maximum(q(load - pv), 0.0)
        surplus = np.maximum(q(pv - load), 0.0)
        if not ren["export_allowed"]:
            raise NotImplementedError("the reference exports PV surplus")
    else:
        pv = np.zeros_like(it)
        net, surplus = load, np.zeros_like(it)

    bat = sim["battery"]
    ck_all = np.zeros_like(it)
    dk_all = np.zeros_like(it)
    soc = np.zeros(n)
    if bat["enabled"]:
        if bat["policy"] != "carbon":
            raise NotImplementedError("the reference runs the carbon policy")
        cap = q(np.asarray(dyn.get("batt_capacity_kwh",
                                   np.full(n, bat["capacity_kwh"])),
                           np.float64))
        rate = q(cap * bat["charge_rate_kw_per_kwh"])
        eff = bat["round_trip_efficiency"]
        threshold, rising = battery_signals(ci, dt, bat["threshold_window_h"])
        threshold = q(threshold)
        below, above = ci < threshold, ci > threshold
        if flip is not None:
            below = np.where(flip, ~below, below)
            above = np.where(flip, ~above & ~(ci == threshold), above)
        want_c = below & rising if bat["wait_for_trough"] else below
        want_d = above
        has = surplus > 0.0
        ccap = np.where(want_c, np.inf, surplus)
        want_c = want_c | has
        want_d = want_d & ~has
        for s in range(s_n):
            ck = np.minimum(rate, np.maximum(q(q(cap - soc) / dt), 0.0))
            ck = np.where(want_c[:, s], np.minimum(ck, ccap[:, s]), 0.0)
            dk = np.minimum(np.minimum(rate, q(soc / dt)), net[:, s])
            dk = np.where(want_d[:, s] & (soc > 0.0) & ~want_c[:, s], dk, 0.0)
            soc = np.clip(q(soc + q(q(q(ck * eff) - dk) * dt)), 0.0, cap)
            ck_all[:, s], dk_all[:, s] = ck, dk
    pv_to_batt = np.minimum(ck_all, surplus)
    grid = q(q(net + q(ck_all - pv_to_batt)) - dk_all)
    export = q(surplus - pv_to_batt)

    out = {
        "it_energy": float(np.sum(it[0])) * dt + np.zeros(n),
        "cooling_energy": np.sum(cooling, axis=1) * dt,
        "pv_energy": np.sum(pv, axis=1) * dt * np.ones(n),
        "batt_discharged": np.sum(dk_all, axis=1) * dt,
        "grid_energy": np.sum(grid, axis=1) * dt,
        "export_energy": np.sum(export, axis=1) * dt,
        "op_carbon": np.sum(q(grid * ci), axis=1) * dt / 1000.0,
        "soc_final": soc,
    }
    pr = sim["pricing"]
    if pr["enabled"]:
        w = max(int(round(pr["billing_window_h"] / dt)), 1)
        n_win = -(-s_n // w)
        padded = np.concatenate([grid, np.zeros((n, n_win * w - s_n))], 1)
        peaks = padded.reshape(n, n_win, w).max(axis=2)
        out["energy_cost"] = np.sum(q(grid * price), axis=1) * dt
        out["demand_cost"] = peaks.sum(axis=1) * pr["demand_charge_per_kw"]
    if precision != "float64":
        out = {key: q(v) for key, v in out.items()}
    return out


def ambiguous_steps(sim: dict, ci, rel: float = 3e-5):
    """bool[N, S]: steps where the battery's carbon comparison lies within
    float32 rounding of the trailing-mean threshold.  The program computes
    that mean in float32 from a running sum of up to 1,344 terms: its error
    measured 2.8e-7 of the mean's magnitude on the CPU, and a running sum
    of n float32 terms can err by up to n x 6e-8; the band is 3e-5 of the
    magnitude.  At such a step either outcome is the configuration's
    arithmetic."""
    bat = sim["battery"]
    if not bat["enabled"]:
        return np.zeros(np.shape(ci), bool)
    ci64 = np.asarray(ci, np.float64)
    threshold, _ = battery_signals(ci64, sim["dt_h"],
                                   bat["threshold_window_h"])
    w = max(int(round(bat["threshold_window_h"] / sim["dt_h"])), 1)
    counts = np.minimum(np.arange(ci64.shape[-1]) + 1, w)
    scale = np.cumsum(ci64, axis=-1) / counts
    return np.abs(ci64 - threshold) <= rel * scale


def facility_outcomes(sim: dict, it_kw, ci, shared: dict, dyn: dict,
                      precision: str = "float64", max_flips: int = 4):
    """Every outcome of the facility chain that float32 arithmetic allows.

    Returns (totals, owner): totals as `facility` returns them over E >= N
    rows, owner i32[E] the scenario of each row.  Rows 0..N-1 are the
    scenarios as computed; a scenario with ambiguous battery steps adds one
    row per nonempty subset of its (at most `max_flips` closest) ambiguous
    steps, with those comparisons inverted."""
    ci = np.asarray(ci)
    n = ci.shape[0]
    amb = ambiguous_steps(sim, ci)
    threshold, _ = battery_signals(ci, sim["dt_h"],
                                   sim["battery"]["threshold_window_h"])
    margin = np.abs(ci - threshold)
    rows, flips = [], []
    for i in np.flatnonzero(amb.any(axis=1)):
        steps = np.flatnonzero(amb[i])
        steps = steps[np.argsort(margin[i, steps])][:max_flips]
        for mask in range(1, 2 ** len(steps)):
            f = np.zeros(ci.shape[1], bool)
            f[steps[[b for b in range(len(steps)) if mask >> b & 1]]] = True
            rows.append(i)
            flips.append(f)
    owner = np.concatenate([np.arange(n), np.asarray(rows, np.int64)])
    flip = np.concatenate([np.zeros_like(ci, bool),
                           np.asarray(flips, bool).reshape(-1, ci.shape[1])])
    dyn_all = {k: np.asarray(v)[owner] for k, v in dyn.items()}
    return facility(sim, it_kw, ci[owner], shared, dyn_all, precision,
                    flip=flip), owner
