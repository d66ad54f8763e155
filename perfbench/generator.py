"""Inputs of a benchmark run, made on the host from `--seed`.

The deployment's task population, its carbon-intensity traces and the
shared weather / price / solar series.  The program under test and the
plain reference both read what this module returns; neither makes its own.

The arithmetic is a copy of the program's generators, kept here so that
no change to the program can change what is measured:
`workloads/synthetic.make_workload` (class-free path),
`carbontraces/synthetic.make_region_traces` and
`benchmarks/bench_simperf._shared_traces`.  Task columns are float32, the
precision the configurations state, so program and reference start from
the same numbers.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Deployment(NamedTuple):
    arrival: np.ndarray    # f32[T] hours, ascending
    duration: np.ndarray   # f32[T] hours at full speed
    cores: np.ndarray      # f32[T]
    gpus: np.ndarray       # f32[T]
    cpu_util: np.ndarray   # f32[T]
    gpu_util: np.ndarray   # f32[T]
    n_hosts: int
    cores_per_host: int
    gpus_per_host: int
    n_steps: int
    dt_h: float


def n_steps(config: dict) -> int:
    w = config["workload"]
    return int(round(w["horizon_days"] * 24 / config["sim"]["dt_h"]))


def _envelope(t_h, w):
    day = 1.0 + w["diurnal_amp"] * np.sin(2 * np.pi * (t_h - 10.0) / 24.0)
    week = 1.0 + w["weekly_amp"] * np.sin(2 * np.pi * (t_h - 48.0) / 168.0)
    return np.maximum(day * week, 0.05)


def deployment(config: dict, seed: int) -> Deployment:
    """The task table of one deployment: Little's-law calibrated arrivals
    at the spec's density, lognormal durations around the published mean,
    cores / GPUs / utilizations drawn per task.  The task count depends on
    the configuration only; the seed draws which tasks."""
    w = config["workload"]
    dt = config["sim"]["dt_h"]
    rng = np.random.default_rng([seed, 0])
    n_hosts = max(int(round(w["n_hosts"] * w["scale"])), 4)
    horizon_h = w["horizon_days"] * 24.0
    choices = np.asarray(w["cores_choices"], np.float64)
    probs = np.asarray(w["cores_probs"], np.float64)
    mean_cores = float(np.dot(choices, probs))
    sig = w["duration_sigma"]
    mu = np.log(w["mean_duration_h"]) - 0.5 * sig * sig
    peak_rel = 1.0 + w["diurnal_amp"] + w["weekly_amp"]
    mean_demand = (w["peak_capacity_frac"] * n_hosts * w["cores_per_host"]
                   / peak_rel)
    lam = mean_demand / (w["mean_duration_h"] * mean_cores)
    n_tasks = int(lam * horizon_h)

    grid = np.arange(0.0, horizon_h, dt)
    cdf = np.cumsum(_envelope(grid, w))
    cdf = cdf / cdf[-1]
    u = np.sort(rng.uniform(0.0, 1.0, n_tasks))
    arrival = np.interp(u, cdf, grid + dt)
    duration = np.clip(rng.lognormal(mu, sig, n_tasks), 0.05, 96.0)
    cores = rng.choice(choices, n_tasks, p=probs)
    is_gpu = rng.uniform(size=n_tasks) < w["gpu_task_frac"]
    gpus = np.where(is_gpu, rng.integers(1, max(w["gpus_per_host"], 1) + 1,
                                         n_tasks), 0).astype(np.float64)
    if w["gpus_per_host"] == 0:
        gpus = np.zeros(n_tasks)
    cpu_util = np.clip(rng.beta(4.0, 2.0, n_tasks), 0.05, 1.0)
    gpu_util = np.where(gpus > 0,
                        np.clip(rng.beta(5.0, 2.0, n_tasks), 0.05, 1.0), 0.0)
    f32 = lambda x: np.asarray(x, np.float32)
    return Deployment(f32(arrival), f32(duration), f32(cores), f32(gpus),
                      f32(cpu_util), f32(gpu_util), n_hosts,
                      int(w["cores_per_host"]), int(w["gpus_per_host"]),
                      n_steps(config), float(dt))


def carbon_traces(n_regions: int, n_steps_: int, dt_h: float,
                  seed: int) -> np.ndarray:
    """f32[R, S] carbon intensity (gCO2/kWh) of R regions drawn from the
    seed: means 15-860, diurnal / weekly / seasonal swings and AR(1) noise
    with the population spread of the paper's 158 regions."""
    rng = np.random.default_rng([seed, 1])
    mean = np.exp(np.log(15.0) + (np.log(860.0) - np.log(15.0))
                  * rng.beta(2.5, 1.6, n_regions))
    greenness = 1.0 - (np.log(mean) - np.log(15.0)) / (
        np.log(860.0) - np.log(15.0))
    mix = 0.3 * greenness + 0.7 * rng.uniform(0.0, 1.0, n_regions)
    daily_amp = np.clip(rng.beta(2.0, 3.0, n_regions) * (0.1 + 1.3 * mix),
                        0.0, 0.6)
    weekly_amp = rng.uniform(0.0, 0.15, n_regions)
    seasonal_amp = rng.uniform(0.0, 0.25, n_regions)
    noise_sigma = rng.uniform(0.02, 0.10, n_regions)
    noise_rho = rng.uniform(0.97, 0.995, n_regions)
    phase_d = rng.uniform(0.0, 24.0, n_regions)
    phase_w = rng.uniform(0.0, 168.0, n_regions)
    t = np.arange(n_steps_) * dt_h
    base = (1.0
            + daily_amp[:, None] * np.sin(
                2 * np.pi * (t[None] - phase_d[:, None]) / 24.0)
            + weekly_amp[:, None] * np.sin(
                2 * np.pi * (t[None] - phase_w[:, None]) / 168.0)
            + seasonal_amp[:, None] * np.sin(2 * np.pi * t[None]
                                             / (24 * 365.25)))
    rho = noise_rho[:, None]
    eps = (rng.standard_normal((n_regions, n_steps_))
           * noise_sigma[:, None] * np.sqrt(1.0 - rho ** 2))
    noise = np.zeros_like(eps)
    acc = np.zeros((n_regions, 1))
    for s in range(n_steps_):
        acc = rho * acc + eps[:, s:s + 1]
        noise[:, s:s + 1] = acc
    ci = mean[:, None] * np.maximum(base + noise, 0.05)
    return ci.astype(np.float32)


def shared_traces(n_steps_: int, dt_h: float) -> dict:
    """The weather / price / solar series every scenario shares (the swept
    axis is the carbon trace): f32[S] each."""
    t = np.arange(n_steps_) * dt_h
    price = (0.1 * (1 + 0.5 * np.sin(2 * np.pi * t / 24))).astype(np.float32)
    wb = (14.0 + 6.0 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    cf = np.clip(np.sin(2 * np.pi * (t - 6.0) / 24.0), 0.0, 1.0).astype(
        np.float32)
    return {"price_trace": price, "wet_bulb_trace": wb, "pv_cf_trace": cf}


class Study(NamedTuple):
    """What one call of a cell computes: a deployment under a grid of
    scenarios.  `ci` holds the carbon trace of every scenario, `dyn` the
    swept scenario values (name -> f32[N]); scenarios are in row-major
    order of `shape`."""
    deployment: Deployment
    ci: np.ndarray          # f32[N, S] carbon trace of each scenario
    dyn: dict               # name -> f32[N]
    shape: tuple            # grid shape, prod(shape) == N
    axes: list              # [(kind, name, f32[L] or f32[L, S])] in order
    shared: dict            # shared_traces()

    @property
    def n_scenarios(self) -> int:
        return int(np.prod(self.shape))


def study(config: dict, traffic: dict, seed: int) -> Study:
    """The inputs of one call of a cell.  `traffic` names the entry point:
    `simulate` runs one scenario in one carbon region drawn from the seed;
    `sweep_grid` runs the product of its `axes`, each either a `trace` axis
    of that many regions drawn from the seed or a `dyn` axis of
    `multipliers`, scaled by `per_host` x the host count where given."""
    dep = deployment(config, seed)
    shared = shared_traces(dep.n_steps, dep.dt_h)
    if traffic["entry"] == "simulate":
        ci = carbon_traces(1, dep.n_steps, dep.dt_h, seed)
        return Study(dep, ci, {}, (1,), [], shared)
    if not any("trace" in ax for ax in traffic["axes"]):
        raise ValueError("a sweep_grid traffic needs a trace axis")
    axes = []
    for ax in traffic["axes"]:
        if "trace" in ax:
            axes.append(("trace", "ci_trace", carbon_traces(
                ax["trace"], dep.n_steps, dep.dt_h, seed)))
        else:
            vals = np.asarray(ax["multipliers"], np.float64)
            if "per_host" in ax:
                vals = vals * ax["per_host"] * dep.n_hosts
            axes.append(("dyn", ax["dyn"], vals.astype(np.float32)))
    shape = tuple(v.shape[0] for _, _, v in axes)
    n = int(np.prod(shape))
    dyn = {}
    for i, (kind, name, vals) in enumerate(axes):
        idx = np.indices(shape).reshape(len(shape), n)[i]
        if kind == "trace":
            ci = vals[idx]
        else:
            dyn[name] = vals[idx]
    return Study(dep, ci, dyn, shape, axes, shared)
