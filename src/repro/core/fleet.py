"""Multi-datacenter fleet engine: R heterogeneous regions as ONE program.

`core/spatial.py` places tasks across regional datacenters; this module runs
the placed fleet: each region has its own carbon trace, weather trace,
battery sizing, cooling setpoint and host count, and the whole fleet is one
jitted `jax.vmap` of the UNCHANGED engine (`core/engine.simulate`) — the
paper's composability claim (C1) at facility granularity.  Per-region
heterogeneity rides on the existing dyn mechanism: host counts through
`n_active_hosts` (horizontal-scaling mask), battery sizing through
`batt_capacity_kwh`/`batt_rate_kw`, climate through per-region wet-bulb
traces, so spatial shifting composes with every other technique, and
`core/grid.py`'s `region_axis`/`fleet_axis` make per-region parameters
sweepable grid dimensions on top.

The contract (differential-tested): a fleet of R=1 regions reproduces
`simulate` on the same workload bit-for-bit, and a fleet grid equals the
per-scenario Python loop of `simulate_fleet` calls.

Placement is host-side and exogenous (traces + task list only); the fleet
program itself is pure jnp, so grids vmap it freely.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import SimConfig
from .engine import _presort_enabled, simulate
from . import telemetry as telemetry_mod
from .metrics import SimResult, fleet_totals, summarize
from .spatial import (spatial_assign, spatial_assign_online, split_by_region)
from .state import HostTable, TaskTable

# dyn keys that may be per-region vectors (length R) in a fleet
PER_REGION_KEYS = ("n_active_hosts", "batt_capacity_kwh", "batt_rate_kw",
                   "cooling_setpoint", "dispatch_lambda", "pv_capacity_kw",
                   "seed")

POLICIES = ("greedy", "spill", "round_robin")


class FleetResult(NamedTuple):
    """`total` aggregates the fleet (metrics.fleet_totals); `per_region` is a
    SimResult whose fields carry a leading (in grids: trailing) R axis."""
    total: SimResult
    per_region: SimResult


class FleetSpec:
    """R regional datacenters: per-region traces, sizing, and a placement
    policy.  Everything per-region is an optional length-R array; scalars
    broadcast.  Arrays live host-side (numpy) — a FleetSpec is scenario
    *structure*, not traced data — and the same spec can be re-run under
    different `dyn` overrides or swept through `core/grid.py`.

    ci_traces:      f32[R, S]  per-region carbon intensity (required)
    wb_traces:      f32[R, S]  per-region wet-bulb weather (needs cooling)
    price_traces:   f32[R, S]  per-region electricity prices (needs pricing)
    pv_traces:      f32[R, S]  per-region solar capacity factors (needs
                               renewables, core/renewables.py)
    n_active_hosts: i32[R]     per-region host count (default: all hosts)
    batt_capacity_kwh, batt_rate_kw, cooling_setpoint, pv_capacity_kw,
    seeds:          f32/i32[R]
    capacity_frac:  float      aggregate core-hour cap per region, as a
                               multiple of its fair (host-count-weighted)
                               share of total work; None = uncapped
    policy:         'greedy' (capped aggregate, core/spatial.py),
                    'spill' (online time-resolved re-routing), or
                    'round_robin' (carbon-blind baseline)
    forecast_h:     placement forecast horizon (hours)
    """

    def __init__(self, ci_traces, wb_traces=None, price_traces=None,
                 pv_traces=None, n_active_hosts=None,
                 batt_capacity_kwh=None, batt_rate_kw=None,
                 cooling_setpoint=None, pv_capacity_kw=None, seeds=None,
                 capacity_frac: float | None = None, policy: str = "greedy",
                 forecast_h: float = 24.0):
        self.ci_traces = np.asarray(ci_traces, np.float32)
        assert self.ci_traces.ndim == 2, (
            f"ci_traces must be f32[R, S], got {self.ci_traces.shape}")
        r = self.ci_traces.shape[0]
        if policy not in POLICIES:
            raise ValueError(f"unknown fleet policy '{policy}'; "
                             f"pick one of {POLICIES}")
        self.wb_traces = None
        if wb_traces is not None:
            self.wb_traces = np.asarray(wb_traces, np.float32)
            assert self.wb_traces.shape[0] == r, (
                f"wb_traces regions {self.wb_traces.shape[0]} != {r}")
        self.price_traces = None
        if price_traces is not None:
            self.price_traces = np.asarray(price_traces, np.float32)
            assert self.price_traces.shape[0] == r, (
                f"price_traces regions {self.price_traces.shape[0]} != {r}")
        self.pv_traces = None
        if pv_traces is not None:
            self.pv_traces = np.asarray(pv_traces, np.float32)
            assert self.pv_traces.shape[0] == r, (
                f"pv_traces regions {self.pv_traces.shape[0]} != {r}")

        def per_region(x, dtype):
            if x is None:
                return None
            a = np.broadcast_to(np.asarray(x, dtype), (r,)).copy()
            return a

        self.n_active_hosts = per_region(n_active_hosts, np.int32)
        self.batt_capacity_kwh = per_region(batt_capacity_kwh, np.float32)
        self.batt_rate_kw = per_region(batt_rate_kw, np.float32)
        self.cooling_setpoint = per_region(cooling_setpoint, np.float32)
        self.pv_capacity_kw = per_region(pv_capacity_kw, np.float32)
        self.seeds = per_region(seeds, np.int32)
        self.capacity_frac = capacity_frac
        self.policy = policy
        self.forecast_h = float(forecast_h)

    @property
    def n_regions(self) -> int:
        return self.ci_traces.shape[0]

    def replace(self, **kw) -> "FleetSpec":
        args = dict(ci_traces=self.ci_traces, wb_traces=self.wb_traces,
                    price_traces=self.price_traces, pv_traces=self.pv_traces,
                    n_active_hosts=self.n_active_hosts,
                    batt_capacity_kwh=self.batt_capacity_kwh,
                    batt_rate_kw=self.batt_rate_kw,
                    cooling_setpoint=self.cooling_setpoint,
                    pv_capacity_kw=self.pv_capacity_kw, seeds=self.seeds,
                    capacity_frac=self.capacity_frac, policy=self.policy,
                    forecast_h=self.forecast_h)
        args.update(kw)
        return FleetSpec(**args)

    def per_region_dyn(self) -> dict:
        """The spec's per-region dyn values as length-R arrays (the leaves
        the fleet vmap maps over)."""
        dyn = {}
        for key, val in (("n_active_hosts", self.n_active_hosts),
                         ("batt_capacity_kwh", self.batt_capacity_kwh),
                         ("batt_rate_kw", self.batt_rate_kw),
                         ("cooling_setpoint", self.cooling_setpoint),
                         ("pv_capacity_kw", self.pv_capacity_kw),
                         ("seed", self.seeds)):
            if val is not None:
                dyn[key] = jnp.asarray(val)
        return dyn

    def region_cores(self, hosts: HostTable) -> np.ndarray:
        """f64[R] concurrent-core capacity per region (first-n active)."""
        cores = np.asarray(hosts.cores, np.float64)
        csum = np.concatenate([[0.0], np.cumsum(cores)])
        if self.n_active_hosts is None:
            return np.full(self.n_regions, csum[-1])
        n = np.clip(self.n_active_hosts, 0, cores.shape[0])
        return csum[n]

    def capacity_core_h(self, tasks: TaskTable, hosts: HostTable):
        """f64[R] aggregate core-hour caps from `capacity_frac`, split in
        proportion to each region's core capacity; None when uncapped."""
        if self.capacity_frac is None:
            return None
        arrival = np.asarray(tasks.arrival)
        valid = np.isfinite(arrival)
        total = float(np.sum((np.asarray(tasks.cores, np.float64)
                              * np.asarray(tasks.duration, np.float64))[valid]))
        share = self.region_cores(hosts)
        share = share / max(share.sum(), 1e-9)
        return self.capacity_frac * total * share


def fleet_place(tasks: TaskTable, hosts: HostTable, fleet: FleetSpec,
                dt_h: float, n_steps: int | None = None) -> np.ndarray:
    """Run the fleet's placement policy.  Returns i32[T] region ids."""
    if fleet.policy == "round_robin":
        arrival = np.asarray(tasks.arrival)
        valid = np.isfinite(arrival)
        region = np.full(arrival.shape[0], -1, np.int32)
        region[valid] = (np.arange(int(valid.sum()))
                        % fleet.n_regions).astype(np.int32)
        return region
    if fleet.policy == "spill":
        return spatial_assign_online(tasks, fleet.ci_traces, dt_h,
                                     fleet.region_cores(hosts),
                                     n_steps=n_steps,
                                     forecast_h=fleet.forecast_h)
    return spatial_assign(tasks, fleet.ci_traces, dt_h,
                          capacity_core_h=fleet.capacity_core_h(tasks, hosts),
                          forecast_h=fleet.forecast_h)


def _vmap_regions(fn, tasks_r: TaskTable, hosts: HostTable, cfg: SimConfig,
                  ci_traces, wb_traces, scalar_dyn, per_region_dyn,
                  price_traces, pv_traces):
    """`fn(tasks, hosts, ci_trace, cfg, dyn=..., weather_trace=...)`
    vmapped over the regions: each region's dyn holds the scalar values,
    its own per-region values and its own price and PV traces."""
    scalar_dyn = dict(scalar_dyn or {})
    ci = jnp.asarray(ci_traces, jnp.float32)
    wb, pr, pv = (None if x is None else jnp.asarray(x, jnp.float32)
                  for x in (wb_traces, price_traces, pv_traces))

    def one(tt, tr, per_r, wb_r, pr_r, pv_r):
        dyn = {**scalar_dyn, **per_r}
        if pr_r is not None:
            dyn["price_trace"] = pr_r
        if pv_r is not None:
            dyn["pv_cf_trace"] = pv_r
        return fn(tt, hosts, tr, cfg, dyn=dyn, weather_trace=wb_r)

    in_axes = (0, 0, 0) + tuple(None if x is None else 0
                                for x in (wb, pr, pv))
    return jax.vmap(one, in_axes=in_axes)(
        tasks_r, ci, dict(per_region_dyn or {}), wb, pr, pv)


def fleet_cell(tasks_r: TaskTable, hosts: HostTable, cfg: SimConfig,
               ci_traces, wb_traces=None, scalar_dyn: dict | None = None,
               per_region_dyn: dict | None = None,
               price_traces=None, pv_traces=None) -> FleetResult:
    """The jit/vmap-safe fleet program over PRE-PLACED stacked tables.

    tasks_r: TaskTable with leading region axis [R, W] (split_by_region).
    scalar_dyn: traced values shared by every region; per_region_dyn: dict
    of length-R arrays, one value per region.  wb_traces/price_traces/
    pv_traces are optional [R, S] per-region weather/tariff/solar families.
    This is the cell the grid engine vmaps — `simulate_fleet` is its
    host-side front door.
    """
    def one(*args, **kw):
        return summarize(simulate(*args, **kw)[0], cfg)

    per = _vmap_regions(one, tasks_r, hosts, cfg, ci_traces, wb_traces,
                        scalar_dyn, per_region_dyn, price_traces, pv_traces)
    return FleetResult(total=fleet_totals(per), per_region=per)


def _fleet_cell_spill(tasks_r: TaskTable, hosts: HostTable, cfg: SimConfig,
                      ci_traces, wb_traces=None, scalar_dyn: dict | None = None,
                      per_region_dyn: dict | None = None,
                      price_traces=None, pv_traces=None) -> FleetResult:
    """`fleet_cell` with the regions COUPLED step-by-step: after every
    simulation step, up to `cfg.resilience.max_spills_per_step` interrupted
    tasks move from failing regions to the healthiest one
    (core/resilience.cross_region_spill) — fleet-level failure-reactive
    placement.

    Structure: `fleet_cell` vmaps the whole `simulate` (scan inside vmap);
    here the nesting flips to scan-of-vmapped-step so the spill hook can
    run between steps with all regions' tables in hand.  Everything but
    the hook is the engine's: `simulate`'s front half
    (`engine.prepare_run`) sets each region up and the stage pipeline's
    step (`engine.build_step_fn`) advances it, both vmapped over regions.
    vmap-of-scan and scan-of-vmap compute the same per-region step math,
    and the spill is a value-preserving no-op while every region is
    healthy, so with no failures this reproduces `fleet_cell` (pinned in
    tests/test_resilience.py).  `simulate_fleet` admits only the
    stage-pipeline backend here, and no priority presort: a spill moves a
    row into the target's first INVALID slot, out of presorted order.
    """
    from . import resilience as resilience_mod
    from .engine import build_step_fn, prepare_run

    states0, inputs, dyn_r, _ = _vmap_regions(
        prepare_run, tasks_r, hosts, cfg, ci_traces, wb_traces, scalar_dyn,
        per_region_dyn, price_traces, pv_traces)
    vstep = jax.vmap(lambda state, inp, dyn:
                     build_step_fn(cfg, None, dyn)(state, inp)[0])
    xs = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), inputs)  # [S, R]
    max_spills = int(cfg.resilience.max_spills_per_step)

    def scan_body(states, inp_t):
        states = vstep(states, inp_t, dyn_r)
        tasks, metrics = resilience_mod.cross_region_spill(
            states.tasks, states.hosts, states.metrics, max_spills)
        return states._replace(tasks=tasks, metrics=metrics), None

    with telemetry_mod.stage_scope("fleet.spill_scan"):
        finals, _ = jax.lax.scan(scan_body, states0, xs, length=cfg.n_steps)
    per = jax.vmap(lambda st: summarize(st, cfg))(finals)
    return FleetResult(total=fleet_totals(per), per_region=per)


def simulate_fleet(tasks: TaskTable, hosts: HostTable, cfg: SimConfig,
                   fleet: FleetSpec, dyn: dict | None = None,
                   region=None, width: int | None = None,
                   jit: bool = True) -> FleetResult:
    """Run R regional datacenters as one compiled vmapped program.

    tasks: ONE fresh task table (as from `make_task_table`) — placement
    happens here, at submission time, via `fleet.policy` (pass `region` to
    override with a precomputed i32[T] assignment).  hosts: the per-region
    host inventory (identical chassis across regions; heterogeneous *counts*
    via `fleet.n_active_hosts`).  `dyn` adds traced values on top of the
    spec: scalars apply to every region, length-R arrays per region.

    Returns a FleetResult: `total` (fleet-aggregated SimResult) and
    `per_region` (leading axis R).  With R=1 this reproduces
    `simulate`+`summarize` bit-for-bit (tests/test_fleet.py).
    """
    if fleet.wb_traces is not None and not cfg.cooling.enabled:
        # same contract as the grid path (ScenarioGrid._check_cfg): refuse
        # to silently drop the per-region weather
        raise ValueError("the fleet carries wb_traces but "
                         "cfg.cooling.enabled is False: the per-region "
                         "weather would be ignored")
    if fleet.price_traces is not None and not cfg.pricing.enabled:
        raise ValueError("the fleet carries price_traces but "
                         "cfg.pricing.enabled is False: the per-region "
                         "prices would be ignored")
    if fleet.pv_traces is not None and not cfg.renewables.enabled:
        raise ValueError("the fleet carries pv_traces but "
                         "cfg.renewables.enabled is False: the per-region "
                         "PV resource would be ignored")
    spill = cfg.resilience.enabled and cfg.resilience.spill_interrupted
    if cfg.resilience.spill_interrupted and not cfg.resilience.enabled:
        raise ValueError("cfg.resilience.spill_interrupted requires "
                         "cfg.resilience.enabled (the spill hook reacts to "
                         "failure signals the resilience loops produce)")
    if spill:
        # the coupled executor steps the stage pipeline; features that
        # change the scan signature are out of scope
        if cfg.backend != "stage-pipeline":
            raise ValueError("spill_interrupted supports only the "
                             f"'stage-pipeline' backend, got {cfg.backend!r}")
        if cfg.probes.enabled or cfg.collect_series:
            raise ValueError("spill_interrupted does not compose with "
                             "probes or collect_series")
        for k in ("arrival_trace", "interactive_frac"):
            if k in (dyn or {}):
                raise ValueError(f"spill_interrupted does not support the "
                                 f"'{k}' dyn key")
        if _presort_enabled(cfg):
            raise ValueError("spill_interrupted does not compose with "
                             "priority levels under first_fit: a spill "
                             "moves a row into the target region's first "
                             "INVALID slot, out of the presorted "
                             "(priority, arrival) row order")
        if width is None:
            # full-width tables so every region has INVALID slots to
            # receive spilled tasks regardless of the initial placement
            width = tasks.n
    if region is None:
        with telemetry_mod.span("fleet.place", policy=fleet.policy):
            region = fleet_place(tasks, hosts, fleet, cfg.dt_h,
                                 n_steps=cfg.n_steps)
    stacked = split_by_region(tasks, region, fleet.n_regions, width=width)
    per_region_dyn = fleet.per_region_dyn()
    scalar_dyn = {}
    for key, val in (dyn or {}).items():
        arr = jnp.asarray(val)
        if key in PER_REGION_KEYS and arr.ndim >= 1:
            assert arr.shape[0] == fleet.n_regions, (
                f"per-region dyn '{key}' has length {arr.shape[0]}, "
                f"fleet has {fleet.n_regions} regions")
            per_region_dyn[key] = arr
        else:
            scalar_dyn[key] = val

    if spill:
        fn = _jitted_fleet_cell_spill if jit else _fleet_cell_spill
    else:
        fn = _jitted_fleet_cell if jit else fleet_cell

    def run():
        return fn(stacked, hosts, cfg, jnp.asarray(fleet.ci_traces),
                  None if fleet.wb_traces is None
                  else jnp.asarray(fleet.wb_traces),
                  scalar_dyn, per_region_dyn,
                  None if fleet.price_traces is None
                  else jnp.asarray(fleet.price_traces),
                  None if fleet.pv_traces is None
                  else jnp.asarray(fleet.pv_traces))

    if telemetry_mod.enabled() and not telemetry_mod.is_tracing(
            (stacked, scalar_dyn, per_region_dyn)):
        with telemetry_mod.run_recorder(
                "fleet", cfg, n_regions=int(fleet.n_regions),
                policy=str(fleet.policy)):
            out = run()
            jax.block_until_ready(out)
        return out
    return run()


# one shared jit cache across simulate_fleet calls: same (shapes, cfg, dyn
# keys) -> same compiled fleet program, so e.g. comparing placement policies
# re-runs one executable instead of recompiling per policy
_jitted_fleet_cell = jax.jit(fleet_cell, static_argnames=("cfg",))
_jitted_fleet_cell_spill = jax.jit(_fleet_cell_spill,
                                   static_argnames=("cfg",))
