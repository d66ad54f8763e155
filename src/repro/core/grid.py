"""Generalized N-dimensional scenario grids: declare axes once, run them all.

The paper's headline result comes from *systematic* exploration — ~5,500
simulations per workload over regions x battery sizes x technique knobs.
`core/sweep.py` used to hard-code three sweep shapes; every new axis meant a
new hand-written vmap wrapper.  This module turns "add a scenario axis" into a
one-line declaration: an N-dimensional grid is a list of `Axis` objects, the
engine composes the nested `jax.vmap`s (axis order = result dimension order),
jits the whole grid into ONE program, optionally chunks the leading axis to
bound memory, and optionally shards the leading axis over a mesh via
`NamedSharding` — the same SPMD layout as the old `sharded_sweep`.

Axis kinds:
  * `trace_axis(traces)` — carbon-region traces `f32[R, S]`; at most one per
    grid (it becomes the `ci_trace` argument of `simulate`).
  * `weather_axis(traces)` — wet-bulb temperature traces `f32[W, S]`
    (weathertraces/synthetic.py) driving the thermal subsystem
    (core/thermal.py); requires `cfg.cooling.enabled`.  Composes a climate
    dimension orthogonal to the carbon-region dimension.
  * `price_axis(traces)` — electricity-price traces `f32[P, S]`
    (pricetraces/synthetic.py) driving the pricing subsystem
    (core/pricing.py): cost accumulation + the battery's price-aware
    dispatch; requires `cfg.pricing.enabled`.  A tariff dimension
    orthogonal to region and climate.
  * `renewable_axis(traces)` — solar capacity-factor traces `f32[V, S]`
    (renewabletraces/synthetic.py) driving the on-site generation
    subsystem (core/renewables.py); requires `cfg.renewables.enabled`.
    A solar-resource dimension orthogonal to region, climate and tariff —
    pair it with `dyn_axis(pv_capacity_kw=...)` for sizing studies.
  * `dyn_axis(**named_values)` — traced scenario scalars fed to the engine as
    dyn ctx keys.  Several names in one call sweep *zipped* (one grid dim);
    separate calls sweep as a cross product (separate dims).  Understood keys:
      - `batt_capacity_kwh`, `batt_rate_kw`  (battery sizing, core/battery.py)
      - `shift_quantile_value`               (shifting threshold, core/shifting.py)
      - `n_active_hosts`                     (horizontal scaling, core/scaling.py)
      - `cooling_setpoint`                   (thermal setpoint, core/thermal.py)
      - `dispatch_lambda`                    (blended battery dispatch weight,
                                              core/battery.py: 1 = carbon,
                                              0 = price arbitrage)
      - `pv_capacity_kw`                     (PV nameplate sizing,
                                              core/renewables.py)
      - `slots_per_step`                     (scheduler placement-slot count,
                                              core/scheduler.py: masked
                                              against the static
                                              cfg.scheduler.slots_per_step
                                              bound, so a slot sweep stays
                                              one compiled program)
      - `interactive_frac`                   (share of tasks re-typed as
                                              interactive inference,
                                              state.with_interactive_frac:
                                              non-shiftable, top priority,
                                              tight SLA grace)
      - `failure_hazard_scale`               (multiplies host AND facility
                                              failure hazards,
                                              core/resilience.py; 0.0 is an
                                              exactly-healthy datacenter, so
                                              one grid can rank techniques
                                              healthy-vs-degraded; requires
                                              `cfg.resilience.enabled`)
      - `throttle_inlet_c`                   (thermal-throttle trip point,
                                              core/resilience.py; requires
                                              `cfg.resilience.enabled`)
      - `pdu_cap_kw`                         (rack power cap applied while a
                                              PDU is down, core/resilience.py;
                                              requires
                                              `cfg.resilience.enabled`)
  * `tasktrace_axis(arrivals)` — per-task arrival sets `f32[A, T]`
    (tasktraces/synthetic.py `make_arrival_sets`): each grid point re-times
    the SAME task population with arrivals sampled from a different
    region's traffic curve (dyn key `arrival_trace`,
    state.retime_task_table).  A demand dimension orthogonal to every
    supply-side axis above.
  * `seed_axis(seeds)` — PRNG seeds for the stochastic failure model.
  * `region_axis(fleet)` — a multi-datacenter FLEET (core/fleet.py): the
    FleetSpec's R regional datacenters (per-region carbon + weather traces,
    host counts, battery sizing, setpoints) run INSIDE every grid cell as
    one vmapped fleet program.  Not a swept dimension: the region axis shows
    up as the TRAILING axis of the result's `per_region` fields, and each
    cell additionally carries fleet-aggregated totals.  Placement (spatial
    shifting) happens once, host-side, when the grid function is built.
  * `fleet_axis(**named_values)` — per-region dyn vectors, values [K, R]:
    the K grid points each supply one length-R vector (e.g. per-region
    host-count products for spatial+HS studies).  Requires a `region_axis`.

Usage — a climate x regions x battery-capacity grid in one program::

    from repro.core.grid import (dyn_axis, seed_axis, sweep_grid, trace_axis,
                                 weather_axis)

    res = sweep_grid(tasks, hosts, cfg, [
        weather_axis(wb_traces),                      # f32[W, S]
        trace_axis(region_traces),                    # f32[R, S]
        dyn_axis(batt_capacity_kwh=caps),             # f32[C]
    ])
    # res is a SimResult whose every field has shape [W, R, C]

    # bound memory / shard over a mesh without touching the axes:
    res = sweep_grid(tasks, hosts, cfg, axes, chunk_size=16)
    res = sweep_grid(tasks, hosts, cfg, axes, mesh=mesh)

    # reduce INSIDE the compiled program (optimal-X studies never
    # materialize the full grid): per-field min/argmin over axis 1
    best = sweep_grid(tasks, hosts, cfg, axes, reduce=("min", 1))
    best_idx = sweep_grid(tasks, hosts, cfg, axes, reduce=("argmin", 1))

A FLEET grid — spatial shifting x horizontal scaling x battery in one
compiled program (each cell is an R-region fleet, results are
FleetResults)::

    fleet = FleetSpec(ci_traces=ci, wb_traces=wb, capacity_frac=1.5)
    res = sweep_grid(tasks, hosts, cfg, [
        fleet_axis(n_active_hosts=counts),            # i32[K, R]
        dyn_axis(batt_capacity_kwh=caps),             # f32[C]
        region_axis(fleet),
    ])
    # res.total.*      : [K, C]      fleet-aggregated
    # res.per_region.* : [K, C, R]   per-datacenter

When `chunk_size` is omitted, it is derived automatically from a
device-memory budget (`memory_budget_bytes`, default from
`$STEAM_SWEEP_MEMORY_BUDGET_MB` or 4 GiB): grids whose estimated working set
fits the budget run unchunked — exactly the old behaviour — while larger
grids chunk instead of OOMing.  The estimate reads the ACTUAL dtypes of the
supplied trace payloads, and every trace-carrying axis accepts
`store='bf16'|'int8'` (core/quant.py) to hold its series quantized in HBM —
half/quarter the bytes, dequantized on read inside each grid cell — which
multiplies the auto-chunk budget accordingly.  Chunked runs donate each
payload slice to the compiled program, so a chunk's input buffers are
reused instead of living alongside its outputs.

The cost-carbon Pareto front in ONE program (battery policy 'blended',
`cfg.pricing.enabled`; see examples/cost_carbon_pareto.py)::

    res = sweep_grid(tasks, hosts, cfg, [
        dyn_axis(dispatch_lambda=lams),               # f32[L] 1=carbon 0=price
        price_axis(price_traces),                     # f32[P, S]
        dyn_axis(batt_capacity_kwh=caps),             # f32[C]
    ], ci_trace=ci)
    # res.total_cost / res.total_carbon_kg have shape [L, P, C]

A PV x battery sizing Pareto over tariffs in ONE program (the renewables
acceptance grid; see examples/renewable_sizing.py)::

    res = sweep_grid(tasks, hosts, cfg, [
        renewable_axis(pv_cf_traces),                 # f32[V, S]
        dyn_axis(pv_capacity_kw=pv_caps),             # f32[K]
        dyn_axis(batt_capacity_kwh=caps),             # f32[C]
        price_axis(tariffs),                          # f32[P, S]
    ], ci_trace=ci)
    # res.total_cost / res.total_carbon_kg have shape [V, K, C, P]

Swept config knobs must be *enabled* statically (`cfg.battery.enabled`,
`cfg.shifting.enabled`, `cfg.cooling.enabled`, `cfg.pricing.enabled`,
`cfg.renewables.enabled`) — the dyn value modulates an enabled technique;
the enable flag itself switches the compiled pipeline.
"""
from __future__ import annotations

import math
import os
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .config import SimConfig
from .engine import StepInputs, simulate
from . import telemetry as telemetry_mod
from .metrics import SimResult, summarize
from .quant import STORES, maybe_dequantize, quantize_trace
from .state import HostTable, TaskTable

TRACE_KEY = "ci_trace"
SEED_KEY = "seed"
TASKTRACE_KEY = "arrival_trace"
WEATHER_KEY = "wet_bulb_trace"
PRICE_KEY = "price_trace"
PV_KEY = "pv_cf_trace"
FLEET_CI_KEY = "fleet_ci_traces"
FLEET_WB_KEY = "fleet_wb_traces"
FLEET_PRICE_KEY = "fleet_price_traces"
FLEET_PV_KEY = "fleet_pv_traces"

_REDUCERS = {"min": jnp.min, "max": jnp.max,
             "argmin": jnp.argmin, "argmax": jnp.argmax}


class Axis(NamedTuple):
    """One grid dimension: `names[j]` is swept with `values[j]` (zipped).

    A value is either a raw array (leading dim = axis length) or a
    `QuantizedTrace` pytree (core/quant.py, trace-carrying axes declared
    with `store=`) whose every leaf shares the leading dim."""

    kind: str                      # 'trace'|'weather'|'price'|'dyn'|'seed'|'fleet'|'region'|'tasktrace'
    names: tuple[str, ...]         # dyn ctx keys (TRACE_KEY / SEED_KEY special)
    values: tuple                  # arrays / QuantizedTraces, equal leading dims
    meta: object = None            # kind-specific payload (region: FleetSpec)

    @property
    def length(self) -> int:
        return jax.tree.leaves(self.values[0])[0].shape[0]


def _stored(traces, store: str):
    """Apply an axis' `store=` choice: raw f32 or a QuantizedTrace pytree."""
    if store == "f32":
        return traces
    if store not in STORES:
        raise ValueError(f"unknown trace store '{store}'; "
                         f"pick one of {STORES}")
    return quantize_trace(traces, store)


def trace_axis(ci_traces, store: str = "f32") -> Axis:
    """Carbon-region axis: ci_traces f32[R, S] -> one grid dim of length R.

    `store='bf16'|'int8'` keeps the series quantized in HBM and dequantizes
    inside each grid cell (core/quant.py) — same for every trace axis below.
    """
    traces = jnp.asarray(ci_traces, jnp.float32)
    assert traces.ndim == 2, f"trace_axis wants f32[R, S], got {traces.shape}"
    return Axis("trace", (TRACE_KEY,), (_stored(traces, store),))


def dyn_axis(**named_values) -> Axis:
    """Traced-scalar axis.  Multiple names sweep zipped along one dimension:
    `dyn_axis(batt_capacity_kwh=caps, batt_rate_kw=rates)` is one axis whose
    i-th point sets both keys; use separate `dyn_axis` calls for a product."""
    if not named_values:
        raise ValueError("dyn_axis needs at least one name=values pair")
    names = tuple(named_values)
    values = tuple(jnp.asarray(v) for v in named_values.values())
    lengths = {v.shape[0] for v in values}
    if len(lengths) != 1:
        raise ValueError(f"zipped dyn_axis values disagree on length: "
                         f"{dict(zip(names, (v.shape for v in values)))}")
    return Axis("dyn", names, values)


def weather_axis(wb_traces, store: str = "f32") -> Axis:
    """Climate axis: wet-bulb traces f32[W, S] -> one grid dim of length W.
    Drives the thermal subsystem; requires `cfg.cooling.enabled`."""
    traces = jnp.asarray(wb_traces, jnp.float32)
    assert traces.ndim == 2, f"weather_axis wants f32[W, S], got {traces.shape}"
    return Axis("weather", (WEATHER_KEY,), (_stored(traces, store),))


def price_axis(price_traces, store: str = "f32") -> Axis:
    """Tariff axis: electricity-price traces f32[P, S] -> one grid dim of
    length P (pricetraces/synthetic.py).  Drives the pricing subsystem
    (core/pricing.py) — cost accumulation and the battery's price-aware
    dispatch policies; requires `cfg.pricing.enabled`.  Composes a tariff
    dimension orthogonal to carbon region and climate."""
    traces = jnp.asarray(price_traces, jnp.float32)
    assert traces.ndim == 2, f"price_axis wants f32[P, S], got {traces.shape}"
    return Axis("price", (PRICE_KEY,), (_stored(traces, store),))


def renewable_axis(pv_cf_traces, store: str = "f32") -> Axis:
    """Solar-resource axis: capacity-factor traces f32[V, S] in [0, 1]
    (renewabletraces/synthetic.py) -> one grid dim of length V.  Drives the
    on-site generation subsystem (core/renewables.py) — PV supply, surplus
    export/curtailment and the battery's surplus-aware dispatch; requires
    `cfg.renewables.enabled`.  Pair with `dyn_axis(pv_capacity_kw=...)` to
    sweep plant sizing against the resource."""
    traces = jnp.asarray(pv_cf_traces, jnp.float32)
    assert traces.ndim == 2, (
        f"renewable_axis wants f32[V, S], got {traces.shape}")
    return Axis("renewable", (PV_KEY,), (_stored(traces, store),))


def tasktrace_axis(arrivals) -> Axis:
    """Workload-arrival axis: per-task arrival sets f32[A, T] -> one grid
    dim of length A (tasktraces/synthetic.py `make_arrival_sets`).  Each
    point re-times the task table with one row of arrival hours
    (state.retime_task_table via the `arrival_trace` dyn key), so one
    compiled grid sweeps WHO the demand is — arrivals following different
    regions' traffic curves — against any supply-side axis.  Rows are
    sorted here, host-side: the table's FIFO invariant is row order, and
    the other task columns keep theirs, so each point is a re-timed
    pairing of the same task population.  T must equal `tasks.n`
    (validated at run time)."""
    arr = jnp.sort(jnp.asarray(arrivals, jnp.float32), axis=-1)
    assert arr.ndim == 2, (
        f"tasktrace_axis wants f32[A, T], got {arr.shape}")
    return Axis("tasktrace", (TASKTRACE_KEY,), (arr,))


def seed_axis(seeds) -> Axis:
    """PRNG-seed axis (stochastic failures replicate across seeds)."""
    return Axis("seed", (SEED_KEY,), (jnp.asarray(seeds, jnp.int32),))


def region_axis(fleet) -> Axis:
    """Fleet axis: the FleetSpec's R regional datacenters run inside every
    grid cell (core/fleet.py).  Not a swept result dimension — per-region
    results appear as the TRAILING axis of `per_region` fields.  Declare it
    after the swept axes (it cannot lead a chunked/sharded grid)."""
    values = (jnp.asarray(fleet.ci_traces, jnp.float32),)
    names = (FLEET_CI_KEY,)
    if fleet.wb_traces is not None:
        values += (jnp.asarray(fleet.wb_traces, jnp.float32),)
        names += (FLEET_WB_KEY,)
    if fleet.price_traces is not None:
        values += (jnp.asarray(fleet.price_traces, jnp.float32),)
        names += (FLEET_PRICE_KEY,)
    if fleet.pv_traces is not None:
        values += (jnp.asarray(fleet.pv_traces, jnp.float32),)
        names += (FLEET_PV_KEY,)
    return Axis("region", names, values, meta=fleet)


def fleet_axis(**named_values) -> Axis:
    """Per-region dyn axis: each value is [K, R] — K grid points, each a
    length-R vector applied region-wise inside the fleet cell (e.g.
    `fleet_axis(n_active_hosts=counts)` sweeps per-region host-count
    products).  Requires a `region_axis` in the same grid; multiple names
    zip along K exactly like `dyn_axis`."""
    if not named_values:
        raise ValueError("fleet_axis needs at least one name=values pair")
    names = tuple(named_values)
    values = tuple(jnp.asarray(v) for v in named_values.values())
    for n, v in zip(names, values):
        if v.ndim != 2:
            raise ValueError(f"fleet_axis '{n}' wants [K, R] values, "
                             f"got shape {v.shape}")
    lengths = {v.shape[0] for v in values}
    if len(lengths) != 1:
        raise ValueError(f"zipped fleet_axis values disagree on length: "
                         f"{dict(zip(names, (v.shape for v in values)))}")
    return Axis("fleet", names, values)


def _normalize_reduce(reduce, ndim: int):
    """Validate a (op, axis) reduction spec; returns (op, positive_axis)."""
    if reduce is None:
        return None
    op, axis = reduce
    if op not in _REDUCERS:
        raise ValueError(f"unknown reduce op '{op}'; "
                         f"pick one of {sorted(_REDUCERS)}")
    axis = int(axis)
    if not -ndim <= axis < ndim:
        raise ValueError(f"reduce axis {axis} out of range for a "
                         f"{ndim}-dimensional grid")
    return op, axis % ndim


def _apply_reduce(fn, red):
    """Wrap the grid fn so each SimResult field is reduced over `axis`
    INSIDE the compiled program (the full grid never reaches HBM)."""
    op, axis = red
    reducer = _REDUCERS[op]

    def reduced(*payloads):
        return jax.tree.map(lambda x: reducer(x, axis=axis), fn(*payloads))

    return reduced


class ScenarioGrid:
    """A validated list of axes; `shape` is the result's leading dimensions."""

    def __init__(self, axes: Sequence[Axis], base_dyn: dict | None = None):
        axes = list(axes)
        if not axes:
            raise ValueError("a ScenarioGrid needs at least one axis")
        seen: set[str] = set()
        for ax in axes:
            for name in ax.names:
                if name in seen:
                    raise ValueError(f"axis name '{name}' declared twice")
                seen.add(name)
        if base_dyn and (dup := seen & set(base_dyn)):
            raise ValueError(f"base dyn keys {sorted(dup)} shadow grid axes")
        regions = [ax for ax in axes if ax.kind == "region"]
        if len(regions) > 1:
            raise ValueError("a grid can hold at most one region_axis")
        self.fleet = regions[0].meta if regions else None
        if self.fleet is not None:
            if axes[0].kind == "region" and len(axes) > 1:
                raise ValueError(
                    "region_axis cannot be the grid's leading axis: declare "
                    "it after the swept axes (chunking/sharding split the "
                    "leading axis, and a fleet must never be split)")
            if any(ax.kind in ("trace", "weather", "price", "renewable")
                   for ax in axes):
                raise ValueError(
                    "region_axis already carries per-region carbon/weather/"
                    "price/pv traces; drop the trace_axis/weather_axis/"
                    "price_axis/renewable_axis")
            if any(ax.kind == "tasktrace" for ax in axes):
                raise ValueError(
                    "tasktrace_axis re-times the task table, but a fleet "
                    "grid splits tasks across regions host-side before the "
                    "compiled program runs: re-timed arrivals could not "
                    "re-place them — sweep arrival sets by building one "
                    "fleet per set instead")
            for ax in axes:
                if ax.kind == "fleet":
                    for n, v in zip(ax.names, ax.values):
                        if v.shape[1] != self.fleet.n_regions:
                            raise ValueError(
                                f"fleet_axis '{n}' has {v.shape[1]} regions, "
                                f"the fleet has {self.fleet.n_regions}")
        elif any(ax.kind == "fleet" for ax in axes):
            raise ValueError("fleet_axis sweeps per-region values: the grid "
                             "also needs a region_axis(fleet)")
        self.axes = axes
        self.base_dyn = dict(base_dyn or {})

    @property
    def shape(self) -> tuple[int, ...]:
        """Leading result dimensions: one per SWEPT axis (the region axis is
        intra-cell — its R shows up trailing on per_region fields)."""
        return tuple(ax.length for ax in self.axes if ax.kind != "region")

    @property
    def n_scenarios(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def has_trace_axis(self) -> bool:
        return any(ax.kind in ("trace", "region") for ax in self.axes)

    def payloads(self) -> tuple:
        return tuple(ax.values for ax in self.axes)

    def grid_fn(self, tasks: TaskTable, hosts: HostTable, cfg: SimConfig,
                ci_trace=None):
        """The composed (unjitted) grid function f(*payloads) -> SimResult.

        Nested vmaps are composed innermost-last so the result's leading
        dimensions follow the axis declaration order.
        """
        if self.has_trace_axis():
            if ci_trace is not None:
                raise ValueError("grid already has a trace_axis; "
                                 "drop the ci_trace argument")
        elif ci_trace is None:
            raise ValueError("no trace_axis in the grid: pass ci_trace")
        axes, base_dyn, fleet = self.axes, self.base_dyn, self.fleet

        if fleet is None:
            def base(*payloads):
                ci = ci_trace
                dyn = dict(base_dyn)
                for ax, vals in zip(axes, payloads):
                    if ax.kind == "trace":
                        ci = maybe_dequantize(vals[0])
                    else:
                        dyn.update((n, maybe_dequantize(v))
                                   for n, v in zip(ax.names, vals))
                final, _ = simulate(tasks, hosts, ci, cfg, dyn=dyn)
                return summarize(final, cfg)
        else:
            # placement is exogenous and happens ONCE, here, host-side: the
            # compiled grid sweeps what the placed fleet *runs*, not where
            # tasks go (sweeping placement itself would re-place per cell)
            from .fleet import fleet_cell, fleet_place
            from .spatial import split_by_region
            region = fleet_place(tasks, hosts, fleet, cfg.dt_h,
                                 n_steps=cfg.n_steps)
            stacked = split_by_region(tasks, region, fleet.n_regions)
            spec_dyn = fleet.per_region_dyn()

            def base(*payloads):
                dyn = dict(base_dyn)
                per_region = dict(spec_dyn)
                ci = wb = pr = pv = None
                for ax, vals in zip(axes, payloads):
                    if ax.kind == "region":
                        named = dict(zip(ax.names, vals))
                        ci = named[FLEET_CI_KEY]
                        wb = named.get(FLEET_WB_KEY)
                        pr = named.get(FLEET_PRICE_KEY)
                        pv = named.get(FLEET_PV_KEY)
                    elif ax.kind == "fleet":
                        per_region.update(zip(ax.names, vals))
                    else:
                        dyn.update(zip(ax.names, vals))
                return fleet_cell(stacked, hosts, cfg, ci, wb,
                                  scalar_dyn=dyn, per_region_dyn=per_region,
                                  price_traces=pr, pv_traces=pv)

        fn = base
        for i in reversed(range(len(axes))):
            if axes[i].kind == "region":
                continue               # intra-cell: replicated, not vmapped
            in_axes = [None] * len(axes)
            in_axes[i] = 0
            fn = jax.vmap(fn, in_axes=tuple(in_axes))
        return fn

    def _check_cfg(self, cfg: SimConfig):
        if (not cfg.cooling.enabled
                and any(ax.kind == "weather" for ax in self.axes)):
            raise ValueError("grid has a weather_axis but cfg.cooling.enabled "
                             "is False: the wet-bulb trace would be ignored")
        if (self.fleet is not None and self.fleet.wb_traces is not None
                and not cfg.cooling.enabled):
            raise ValueError("the fleet carries wb_traces but "
                             "cfg.cooling.enabled is False: the per-region "
                             "weather would be ignored")
        if (not cfg.pricing.enabled
                and any(ax.kind == "price" for ax in self.axes)):
            raise ValueError("grid has a price_axis but cfg.pricing.enabled "
                             "is False: the price trace would be ignored")
        if (self.fleet is not None and self.fleet.price_traces is not None
                and not cfg.pricing.enabled):
            raise ValueError("the fleet carries price_traces but "
                             "cfg.pricing.enabled is False: the per-region "
                             "prices would be ignored")
        if (not cfg.renewables.enabled
                and any(ax.kind == "renewable" for ax in self.axes)):
            raise ValueError("grid has a renewable_axis but "
                             "cfg.renewables.enabled is False: the PV "
                             "capacity-factor trace would be ignored")
        if (self.fleet is not None and self.fleet.pv_traces is not None
                and not cfg.renewables.enabled):
            raise ValueError("the fleet carries pv_traces but "
                             "cfg.renewables.enabled is False: the "
                             "per-region PV resource would be ignored")

    def _check_tasks(self, tasks: TaskTable):
        for ax in self.axes:
            if ax.kind == "tasktrace" and ax.values[0].shape[1] != tasks.n:
                raise ValueError(
                    f"tasktrace_axis carries {ax.values[0].shape[1]} "
                    f"arrivals per point but the task table has {tasks.n} "
                    "rows: generate the arrival sets with "
                    "n_tasks == tasks.n (retiming is a bijection on rows)")

    def run(self, tasks: TaskTable, hosts: HostTable, cfg: SimConfig,
            ci_trace=None, *, chunk_size: int | None = None, mesh=None,
            jit: bool = True, reduce: tuple[str, int] | None = None,
            memory_budget_bytes: float | None = None) -> SimResult:
        """Evaluate the whole grid.  Returns a SimResult with leading
        dimensions `self.shape` (minus the reduced axis, if any).

        chunk_size: split the LEADING axis into chunks of at most this many
          points, running one compiled program per chunk (bounds peak memory;
          equal-size chunks share one compilation, a ragged tail adds one).
          When omitted, a chunk size is derived from `memory_budget_bytes`
          ($STEAM_SWEEP_MEMORY_BUDGET_MB, default 4 GiB): grids whose
          estimated working set fits run unchunked.
        mesh: shard the leading axis over the mesh's ('pod','data') axes with
          NamedSharding — the production SPMD path.  Combined with
          chunk_size, chunks are rounded up to a multiple of the mesh's
          device count (sharding needs every chunk to divide evenly).
        reduce: (op, axis) with op in {'min','max','argmin','argmax'} —
          reduce every SimResult field over that grid axis INSIDE the
          compiled program, so optimal-battery-style studies never
          materialize the full grid.  The reduced axis must not be the
          leading one when the run is chunked.
        """
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self._check_cfg(cfg)
        self._check_tasks(tasks)
        red = _normalize_reduce(reduce, len(self.shape))
        with telemetry_mod.span("grid.build", shape=str(self.shape)):
            fn = self.grid_fn(tasks, hosts, cfg, ci_trace)
            if red is not None:
                fn = _apply_reduce(fn, red)
            payloads = self.payloads()
        recording = (telemetry_mod.enabled()
                     and not telemetry_mod.is_tracing((tasks, hosts,
                                                       payloads)))
        if not recording:
            return self._run_grid(tasks, hosts, cfg, fn, payloads, chunk_size,
                                  mesh, jit, red, memory_budget_bytes, None)
        with telemetry_mod.run_recorder("grid", cfg) as rec:
            rec.grid_shape = [int(s) for s in self.shape]
            rec.extra["n_scenarios"] = int(self.n_scenarios)
            rec.extra["axes"] = [{"kind": ax.kind, "names": list(ax.names),
                                  "length": ax.length} for ax in self.axes]
            rec.trace_dtypes = {
                ax.names[0]: str(jnp.asarray(
                    jax.tree.leaves(ax.values[0])[0]).dtype)
                for ax in self.axes
                if ax.kind in ("trace", "weather", "price", "renewable")}
            if mesh is not None:
                rec.mesh = {"axis_names": [str(a) for a in mesh.axis_names],
                            "shape": [int(s) for s in mesh.devices.shape]}
            out = self._run_grid(tasks, hosts, cfg, fn, payloads, chunk_size,
                                 mesh, jit, red, memory_budget_bytes, rec)
            jax.block_until_ready(out)
        return out

    def _run_grid(self, tasks, hosts, cfg, fn, payloads, chunk_size, mesh,
                  jit, red, memory_budget_bytes, rec):
        """`run`'s execution body; `rec` is the telemetry record builder
        (None when telemetry is off or the call is being traced)."""
        if self.axes[0].kind == "region":
            # a lone region_axis: nothing is swept, so nothing to chunk or
            # shard — the fleet's internal region vmap must never be split
            if mesh is not None:
                raise ValueError("cannot shard a grid whose only axis is the "
                                 "region_axis: add a swept leading axis")
            fn = jax.jit(fn) if jit else fn
            with telemetry_mod.span("grid.execute"):
                return fn(*payloads)
        auto_chunked = chunk_size is None
        if auto_chunked:
            chunk_size = self._auto_chunk_size(tasks, hosts, cfg,
                                               memory_budget_bytes)
        if mesh is not None:
            chunk_size = _round_chunk_to_mesh(mesh, chunk_size)
        if (red is not None and red[1] == 0
                and self.axes[0].length > chunk_size):
            # guard the documented footgun up front: per-chunk reductions
            # over the split axis cannot be stitched back together, and
            # letting it run fails with a shape error deep inside the scan
            cause = ("chunk size auto-derived from the memory budget"
                     if auto_chunked else "explicit chunk_size")
            raise ValueError(
                f"reduce=({red[0]!r}, 0) targets the leading axis of a "
                f"chunked run (leading length {self.axes[0].length}, "
                f"chunks of {chunk_size}: {cause}): move the reduced axis "
                "off axis 0, raise the memory budget, or pass an explicit "
                "chunk_size >= the leading length")
        lead = self.axes[0].length
        if rec is not None:
            # chunk plan with predicted (estimate-based) vs actual bytes
            rec.chunk = {
                "chunk_size": int(chunk_size),
                "n_chunks": -(-lead // chunk_size),
                "auto": bool(auto_chunked),
                "predicted_bytes_per_lead": float(
                    self._per_lead_bytes(tasks, hosts, cfg)),
                "actual_payload_bytes": int(sum(
                    jnp.asarray(l).size * jnp.asarray(l).dtype.itemsize
                    for p in payloads for l in jax.tree.leaves(p))),
            }
        if mesh is not None:
            return self._run_sharded(fn, payloads, mesh, chunk_size, red)
        if lead <= chunk_size:
            with telemetry_mod.span("grid.execute", chunks=1):
                return (jax.jit(fn) if jit else fn)(*payloads)
        # donate each chunk's payload slice: the slices are temporaries, so
        # XLA may reuse their buffers for the chunk's outputs instead of
        # holding both live — the chunked path exists to bound memory.
        # Donation is best-effort (a bf16/int8 chunk has no f32 output to
        # fold into), so the unusable-buffer warning is suppressed.
        cfn = jax.jit(fn, donate_argnums=(0,)) if jit else fn
        # equal-size chunks must share one compilation (a ragged tail adds
        # one more); a compile per chunk is the slots_per_step bug class
        ragged = lead % chunk_size != 0
        guard = telemetry_mod.recompile_guard(
            "grid.run chunk loop", allowed=1 + int(ragged))
        chunks = []
        with warnings.catch_warnings(), guard:
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for i, s in enumerate(range(0, lead, chunk_size)):
                with telemetry_mod.span("grid.chunk", index=i, start=s):
                    # slice OUTSIDE the guard window: eager slice ops compile
                    # per static offset and are not chunk recompiles
                    p0 = _slice_lead(payloads[0], s, chunk_size)
                    guard.mark()
                    chunks.append(cfn(p0, *payloads[1:]))
                guard.tick()
            return _concat_chunks(chunks)

    def _per_lead_bytes(self, tasks, hosts, cfg: SimConfig) -> float:
        """Estimated working-set bytes per leading-axis point.

        Bytes per grid cell = the vmapped scan carry (task + host tables,
        double-buffered by the scan) + the per-cell StepInputs series + the
        cell's slice of the output pytree (SimResult: one scalar per field,
        plus the probe-bus ring when cfg.probes is on).
        """
        carry_bytes = sum(jnp.asarray(x).size * jnp.asarray(x).dtype.itemsize
                          for x in (*jax.tree.leaves(tasks),
                                    *jax.tree.leaves(hosts)))
        # per-point bytes of the SUPPLIED series come from the payloads'
        # actual dtypes (a store='bf16'/'int8' axis is cheaper than f32, and
        # seed/dyn scalars cost ~nothing — the old estimate priced every
        # StepInputs field at f32[S] regardless of what was supplied);
        # unsupplied StepInputs fields are derived f32[S] series
        supplied = 0
        supplied_bytes = 0
        for ax in self.axes:
            if ax.kind not in ("trace", "weather", "price", "renewable"):
                continue               # dyn/seed/fleet points are ~scalars
            supplied += 1
            supplied_bytes += sum(
                leaf.size // ax.length * leaf.dtype.itemsize
                for v in ax.values for leaf in jax.tree.leaves(v))
        derived = len(StepInputs._fields) - supplied
        inputs_bytes = supplied_bytes + derived * cfg.n_steps * 4
        out_bytes = (len(SimResult._fields) - 1) * 4
        if cfg.probes.enabled:
            out_bytes += len(telemetry_mod.Probes._fields) * 4 * (
                telemetry_mod.probe_capacity(cfg.n_steps, cfg.probes))
        per_cell = 2 * carry_bytes + inputs_bytes + out_bytes
        if self.fleet is not None:
            # every cell runs R regional engines (stacked tables + inputs)
            per_cell *= self.fleet.n_regions
        lead = self.axes[0].length
        return per_cell * (self.n_scenarios / max(lead, 1))

    def _auto_chunk_size(self, tasks, hosts, cfg: SimConfig,
                         budget_bytes: float | None) -> int:
        """Chunk size from a device-memory budget (ROADMAP auto-chunking).

        The leading axis is chunked so `chunk * cells_per_leading_point *
        bytes_per_cell` (see `_per_lead_bytes`) fits the budget; a grid
        under budget returns its full leading length (i.e. runs unchunked,
        the legacy behaviour).
        """
        if budget_bytes is None:
            budget_bytes = float(os.environ.get(
                "STEAM_SWEEP_MEMORY_BUDGET_MB", 4096)) * 2**20
        lead = self.axes[0].length
        per_lead = self._per_lead_bytes(tasks, hosts, cfg)
        return max(1, min(lead, int(budget_bytes // max(per_lead, 1.0))))

    def _shardings(self, mesh, red=None):
        """(in_shardings, out_sharding, lead, repl) for this grid on `mesh`."""
        axes = _lead_axes(mesh)
        lead = NamedSharding(mesh, P(axes))
        repl = NamedSharding(mesh, P())
        in_sh = tuple(
            jax.tree.map(lambda _: lead if i == 0 else repl, p)
            for i, p in enumerate(self.payloads()))
        n = len(self.shape)  # swept dims only; per_region trailing axes of a
        # fleet grid are shorter than the spec and stay replicated
        if red is None:
            out_spec = P(axes, *(None,) * (n - 1))
        elif red[1] == 0:  # the sharded axis is reduced away -> replicated
            out_spec = P(*(None,) * (n - 1))
        else:
            out_spec = P(axes, *(None,) * (n - 2))
        return in_sh, NamedSharding(mesh, out_spec), lead, repl

    def _run_sharded(self, fn, payloads, mesh, chunk_size, red=None):
        # chunk_size arrives already rounded to a device multiple
        # (_round_chunk_to_mesh in `run`), so the leading-axis reduce guard
        # and the actual chunking agree on what gets split
        in_sh, out_sh, lead, repl = self._shardings(_auto_mesh(mesh), red)
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)

        def run_chunk(p0):
            args = (jax.device_put(p0, lead),) + tuple(
                jax.device_put(p, repl) for p in payloads[1:])
            return jfn(*args)

        if chunk_size is None or self.axes[0].length <= chunk_size:
            return run_chunk(payloads[0])
        return _concat_chunks(
            [run_chunk(_slice_lead(payloads[0], s, chunk_size))
             for s in range(0, self.axes[0].length, chunk_size)])

    def shard_map_callable(self, tasks: TaskTable, hosts: HostTable,
                           cfg: SimConfig, ci_trace=None, *, mesh=None,
                           donate: bool = True):
        """Build the weak-scaling executor: `f(*payloads) -> SimResult`.

        The returned callable places each leading-axis chunk of
        ``lead / n_devices`` grid cells on its own device via
        :func:`jax.shard_map` — every device runs
        the SAME per-shard program on its local block, with no collectives
        (grid cells are independent), so weak scaling (cells ∝ devices)
        holds the per-device working set and per-device wall time constant.
        The sharded payload is donated (``donate=True``) so each call's
        input block buffer can be reused for its output on device —
        matching the chunked executor's donation discipline.  Pass
        ``donate=False`` when the SAME payload arrays will be re-submitted
        (e.g. repeated benchmark timing calls).

        Build once, call many times: the jit wrapper is created here, not
        per call, so repeated invocations hit the executable cache.
        """
        if self.axes[0].kind == "region":
            raise ValueError("cannot shard a grid whose leading axis is the "
                             "region_axis: add a swept leading axis")
        mesh = _auto_mesh(mesh)
        spec = P(_lead_axes(mesh))
        ndev = _lead_devices(mesh)
        lead = self.axes[0].length
        if lead % ndev:
            raise ValueError(
                f"shard_map executor: leading axis ({lead} cells) must "
                f"divide evenly over the mesh's {ndev} devices — pad the "
                f"axis or size the grid as cells = k * device_count")
        fn = self.grid_fn(tasks, hosts, cfg, ci_trace)
        n_pay = len(self.axes)
        in_specs = tuple(spec if i == 0 else P() for i in range(n_pay))
        sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
                           check_vma=False)
        jfn = jax.jit(sm, donate_argnums=(0,) if donate else ())
        lead_sh = NamedSharding(mesh, spec)
        repl_sh = NamedSharding(mesh, P())

        def call(*payloads):
            args = (jax.device_put(payloads[0], lead_sh),) + tuple(
                jax.device_put(p, repl_sh) for p in payloads[1:])
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return jfn(*args)

        return call

    def run_shard_map(self, tasks: TaskTable, hosts: HostTable,
                      cfg: SimConfig, ci_trace=None, *, mesh=None,
                      donate: bool = True) -> SimResult:
        """Evaluate the grid with the shard_map weak-scaling executor.

        Same contract as :meth:`run` (leading result dims = ``self.shape``)
        with the leading axis split one-chunk-per-device instead of looped
        host-side; requires ``lead % device_count == 0``.  At one device the
        compiled per-shard program sees exactly the shapes the single-device
        chunked path compiles, so the results are bitwise-equal
        (tests/test_grid.py pins this).
        """
        self._check_cfg(cfg)
        self._check_tasks(tasks)
        mesh = _auto_mesh(mesh)
        with telemetry_mod.span("grid.build", shape=str(self.shape),
                                executor="shard_map"):
            call = self.shard_map_callable(tasks, hosts, cfg, ci_trace,
                                           mesh=mesh, donate=donate)
            payloads = self.payloads()
        recording = (telemetry_mod.enabled()
                     and not telemetry_mod.is_tracing((tasks, hosts,
                                                       payloads)))
        if not recording:
            with telemetry_mod.span("grid.execute", executor="shard_map"):
                return call(*payloads)
        with telemetry_mod.run_recorder("grid", cfg) as rec:
            rec.grid_shape = [int(s) for s in self.shape]
            rec.extra["executor"] = "shard_map"
            rec.extra["n_scenarios"] = int(self.n_scenarios)
            rec.mesh = {"axis_names": [str(a) for a in mesh.axis_names],
                        "shape": [int(s) for s in mesh.devices.shape]}
            ndev = _lead_devices(mesh)
            rec.chunk = {
                "chunk_size": int(self.axes[0].length // ndev),
                "n_chunks": int(ndev),
                "auto": False,
                "predicted_bytes_per_lead": float(
                    self._per_lead_bytes(tasks, hosts, cfg)),
                "actual_payload_bytes": int(sum(
                    jnp.asarray(l).size * jnp.asarray(l).dtype.itemsize
                    for p in payloads for l in jax.tree.leaves(p))),
            }
            with telemetry_mod.span("grid.execute", executor="shard_map"):
                out = call(*payloads)
            jax.block_until_ready(out)
        return out

    def lower(self, tasks: TaskTable, hosts: HostTable, cfg: SimConfig,
              ci_trace=None, *, mesh=None,
              reduce: tuple[str, int] | None = None):
        """Lower (without running) the whole-grid program.

        Generalizes the old region-only `lower_sweep`: ANY declared grid —
        climate x region x battery, reductions included — lowers to one
        program whose compiled HLO feeds the roofline analyzer
        (launch/hlo_analysis.analyze) and dry-run memory analysis.  Payload
        values are passed abstractly (ShapeDtypeStructs), so lowering a
        paper-scale grid allocates nothing.
        """
        self._check_cfg(cfg)
        self._check_tasks(tasks)
        red = _normalize_reduce(reduce, len(self.shape))
        fn = self.grid_fn(tasks, hosts, cfg, ci_trace)
        if red is not None:
            fn = _apply_reduce(fn, red)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.payloads())
        if mesh is None:
            return jax.jit(fn).lower(*abstract)
        in_sh, out_sh, _, _ = self._shardings(_auto_mesh(mesh), red)
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        return jfn.lower(*abstract)


def _auto_mesh(mesh=None) -> Mesh:
    """The mesh a grid runs on, with every axis in `Auto` mode.

    Grid cells are independent, so placement is fully described by the
    in/out shardings and no context mesh is needed.  `jax.make_mesh`
    returns `Explicit` axes, which would put shardings into the traced
    types of every op inside the cells; the grid asks for none of that.
    None means one 'data' axis over all devices."""
    if mesh is None:
        return jax.make_mesh((jax.device_count(),), ("data",),
                             axis_types=(AxisType.Auto,))
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _lead_axes(mesh) -> tuple[str, ...]:
    """The mesh axes the leading grid dim shards over, as a plain tuple
    (PartitionSpec normalizes a 1-tuple entry to the bare name)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _lead_devices(mesh) -> int:
    """Device count along the mesh axes the leading dim shards over."""
    return math.prod(mesh.shape[a] for a in _lead_axes(mesh))


def _round_chunk_to_mesh(mesh, chunk_size: int) -> int:
    """NamedSharding requires each chunk's leading dim to divide evenly over
    the mesh devices; round the chunk up to a device multiple (the total
    leading length must divide too, as in any sharded sweep — then every
    chunk including the tail stays divisible)."""
    ndev = _lead_devices(mesh)
    return max(ndev, -(-chunk_size // ndev) * ndev)


def _slice_lead(axis_values: tuple, start: int, size: int) -> tuple:
    """Slice one chunk out of the leading axis' values (array or
    QuantizedTrace pytree alike)."""
    return tuple(jax.tree.map(lambda x: x[start:start + size], v)
                 for v in axis_values)


def _concat_chunks(parts: list[SimResult]) -> SimResult:
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def sweep_grid(tasks: TaskTable, hosts: HostTable, cfg: SimConfig,
               axes: Sequence[Axis], ci_trace=None, *,
               dyn: dict | None = None, chunk_size: int | None = None,
               mesh=None, jit: bool = True,
               reduce: tuple[str, int] | None = None,
               memory_budget_bytes: float | None = None,
               executor: str = "chunked") -> SimResult:
    """One-call entry point: `sweep_grid(tasks, hosts, cfg, [axis, ...])`.

    `dyn` holds fixed (non-swept) traced scenario values applied to every grid
    point, e.g. `dyn={"n_active_hosts": 12}` to run the whole grid on a
    down-scaled datacenter.  `reduce=(op, axis)` folds an axis inside the
    compiled program.  See the module docstring for the axis zoo.

    `executor="shard_map"` routes through the weak-scaling executor
    (`ScenarioGrid.run_shard_map`): one leading-axis chunk per device via
    `shard_map`, donated buffers, `lead % device_count == 0` required;
    `chunk_size` / `reduce` / `memory_budget_bytes` do not apply there.
    """
    grid = ScenarioGrid(axes, base_dyn=dyn)
    if executor == "shard_map":
        if chunk_size is not None or reduce is not None:
            raise ValueError("executor='shard_map' places one chunk per "
                             "device: chunk_size/reduce do not apply")
        return grid.run_shard_map(tasks, hosts, cfg, ci_trace, mesh=mesh)
    if executor != "chunked":
        raise ValueError(f"unknown executor {executor!r}; "
                         f"pick 'chunked' or 'shard_map'")
    return grid.run(tasks, hosts, cfg, ci_trace, chunk_size=chunk_size,
                    mesh=mesh, jit=jit, reduce=reduce,
                    memory_budget_bytes=memory_budget_bytes)
