"""The STEAM engine: composable stage pipeline + lax.scan executor.

This is the paper's component-graph composability (§IV-B) adapted to TPU: a
simulation step is a *pipeline* of pure stages `(state, ctx) -> (state, ctx)`.
Each sustainability technique is one stage; enabling a technique means adding
its stage to the pipeline (neighbouring stages communicate through ctx keys,
mirroring the supplier/consumer edges of the component graph).  Because the
pipeline is composed at trace time, XLA fuses the entire step — there is no
runtime dispatch.

Default pipeline (order matters and mirrors OpenDC's event cascade):
  failures -> checkpoint -> task_stopper -> shifting_gate -> scheduler
  -> progress -> utilization -> power -> cooling -> renewables -> battery
  -> pricing -> carbon -> metrics

Power flows between the facility stages travel on an explicit **energy-flow
ledger** (`ctx["flow"]`, an `EnergyFlow` pytree) instead of ad-hoc scalar
ctx keys: each stage reads and writes named ledger fields, and the ledger
obeys a per-step conservation law (checked in tests/test_energy_ledger.py,
not at runtime)

    grid_import + pv + batt_discharge
        == it + cooling + batt_charge + grid_export + curtailed

Ledger field glossary (all kW, one value per step):

  it_kw             IT-equipment draw (stage_power: hosts + accelerators)
  cooling_kw        cooling overhead (stage_cooling; 0 with cooling off)
  pv_kw             on-site PV generation (stage_renewables; 0 when off)
  batt_charge_kw    power flowing INTO the battery (PV surplus first,
                    grid top-up only when the dispatch policy asks)
  batt_discharge_kw battery power serving facility load
  grid_import_kw    metered grid draw — what carbon, pricing and
                    peak-power accounting all meter
  grid_export_kw    PV surplus sold to the grid (export tariff leg)
  curtailed_kw      PV surplus thrown away (export not allowed / no takers)

`stage_cooling` (cfg.cooling.enabled) sits between power and battery so that
battery peak-shaving and carbon accounting operate on *facility* power
(IT + weather-driven cooling overhead), not just IT power.
`stage_renewables` (cfg.renewables.enabled) supplies PV between cooling and
battery, so generation first serves the facility load and the battery
dispatches on the *net* load (charging preferentially from surplus,
core/battery.surplus_aware_dispatch); without a battery, `stage_net_meter`
settles the surplus into export or curtailment.  `stage_pricing`
(cfg.pricing.enabled) sits after the battery so the electricity bill —
energy charge plus billing-window demand charge, minus export revenue
(core/pricing.py) — meters the battery-shaped grid draw.  `stage_carbon`
always meters `grid_import_kw`, which with renewables on is the NET import:
on-site generation displaces operational carbon one-for-one, exports earn
money but no carbon credit (location-based accounting).

Kernel backends
---------------
`cfg.backend` selects the step executor.  Every executor shares one
assembly, written once in this module: `prepare_run` (`simulate`'s front
half: dyn checks, task-table shaping, `StepInputs`, initial state),
`step_ctx` (a step's ctx from its inputs), `_run_stages` (the scoped
stage loop) and `_series` (the `collect_series` outputs).  The stages
themselves are the same functions on every path.

  * ``stage-pipeline`` (default) — the scan above: one `lax.scan` whose
    step (`build_step_fn`) runs every stage, dragging the full task/host
    tables through all S steps.  Maximum composability (custom `stages`
    land here).  The fleet spill executor (core/fleet.py) vmaps
    `prepare_run` and this step over regions.
  * ``megakernel`` — the same simulation split at its one true sequential
    boundary.  The DEMAND phase (`_build_demand_step`: `demand_stages`,
    then `stage_it_power` and, with resilience on, `stage_resilience`)
    still scans, because placement is genuinely recurrent; it emits only
    `it_kw[S]`.  The FACILITY phase (cooling ->
    renewables -> battery -> pricing -> carbon) is elementwise in t except
    for two scalar recurrences (battery SoC, billing-window peak), so it
    runs as [S]-wide vector math with a scalar-carry scan
    (kernels/ref.fused_facility_chain) — and, with `cfg.use_pallas`, as ONE
    time-blocked Pallas kernel (kernels/fused_step.py) that keeps the
    SoC/window-peak carries in VMEM across time blocks and emits only
    per-block metric partial sums to HBM.  Two wins: the facility math
    vectorizes over the horizon, and under `vmap` over trace/price/PV axes
    the demand scan has no batched inputs (the shifting gate reads the CI
    trace only when `cfg.shifting.enabled`), so XLA hoists it and computes
    demand ONCE per batch instead of per scenario.

    Equivalence contract: megakernel == stage-pipeline within float
    tolerance (sums reassociate: rtol ~1e-5; the per-step flow SERIES
    are the same arithmetic scheduled differently, so they agree to ULP-
    level rounding and the EnergyFlow conservation law holds on the fused
    path to the same tolerance as on the stage path).  Differentially
    tested over all 2^3 cooling x pricing x renewables combos x dispatch
    policies in tests/test_megakernel.py.

    Quantized-trace accuracy: the Pallas path stores the four exogenous
    traces (CI, wet-bulb, price, PV-cf) as bf16 or int8 with
    dequant-on-read (core/quant.py).  bf16 keeps relative error <= 2^-8
    (~0.4%); int8 affine quantization bounds absolute error by
    trace_range/510.  Both are below trace calibration uncertainty; pass
    trace_store='f32' to the kernel for exact inputs.

  Pallas kernels themselves run in interpret mode iff the platform the
  call runs on is CPU (kernels/ops.resolved_interpret), resolved per call
  — never pinned at import.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from . import battery as battery_mod
from . import carbon as carbon_mod
from . import failures as failures_mod
from . import pricing as pricing_mod
from . import renewables as renewables_mod
from . import resilience as resilience_mod
from . import scaling as scaling_mod
from . import scheduler as scheduler_mod
from . import shifting as shifting_mod
from . import telemetry as telemetry_mod
from . import thermal as thermal_mod
from .config import HOURS_PER_YEAR, SimConfig
from .power import host_power_kw
from .state import (DONE, PENDING, RUNNING, BatteryState, HostTable,
                    SimState, TaskTable, init_sim_state, inverse_permutation,
                    permute_task_table, priority_schedule_order,
                    retime_task_table, with_interactive_frac)

BACKENDS = ("stage-pipeline", "megakernel")

Stage = Callable[[SimState, dict], tuple[SimState, dict]]


class EnergyFlow(NamedTuple):
    """Per-step facility power ledger (kW) — see the module docstring for
    the field glossary and the conservation law the fields obey."""
    it_kw: jax.Array
    cooling_kw: jax.Array
    pv_kw: jax.Array
    batt_charge_kw: jax.Array
    batt_discharge_kw: jax.Array
    grid_import_kw: jax.Array
    grid_export_kw: jax.Array
    curtailed_kw: jax.Array


def init_energy_flow() -> EnergyFlow:
    z = jnp.float32(0.0)
    return EnergyFlow(it_kw=z, cooling_kw=z, pv_kw=z, batt_charge_kw=z,
                      batt_discharge_kw=z, grid_import_kw=z,
                      grid_export_kw=z, curtailed_kw=z)


class StepInputs(NamedTuple):
    """Exogenous per-step inputs (the xs of the scan), all precomputed."""
    ci: jax.Array              # f32[S] carbon intensity gCO2/kWh
    batt_threshold: jax.Array  # f32[S]
    ci_rising: jax.Array       # bool[S]
    shift_threshold: jax.Array # f32[S]
    wet_bulb_c: jax.Array      # f32[S] wet-bulb temperature (cooling weather)
    price: jax.Array           # f32[S] electricity price (currency/kWh)
    price_lo: jax.Array        # f32[S] forward charge-quantile band
    price_hi: jax.Array        # f32[S] forward discharge-quantile band
    pv_cf: jax.Array           # f32[S] solar capacity factor in [0, 1]
    # facility failure injection (core/resilience.py): both series depend
    # only on the seed, never on simulation state, so they are exogenous
    # inputs — identical for both backends, vectorizable in the megakernel
    chiller_derate: jax.Array  # f32[S] COP/economizer scale (1 = healthy)
    pdu_cap_kw: jax.Array      # f32[S] rack-power clamp (+inf = healthy)


def build_step_inputs(ci_trace, cfg: SimConfig,
                      dyn: dict | None = None) -> StepInputs:
    dyn = dyn or {}
    ci = jnp.asarray(ci_trace, jnp.float32)
    assert ci.shape[0] >= cfg.n_steps, (
        f"carbon trace too short: {ci.shape[0]} < {cfg.n_steps}")
    ci = ci[: cfg.n_steps]
    bt, rising = battery_mod.precompute_battery_signals(ci, cfg.dt_h, cfg.battery)
    st = (shifting_mod.precompute_shift_threshold(
              ci, cfg.dt_h, cfg.shifting,
              quantile=dyn.get("shift_quantile_value"))
          if cfg.shifting.enabled else jnp.zeros_like(ci))
    wb = dyn.get("wet_bulb_trace")
    if wb is None:
        wb = jnp.full_like(ci, cfg.cooling.setpoint_c)  # weatherless: worst case
    else:
        wb = jnp.asarray(wb, jnp.float32)
        assert wb.shape[0] >= cfg.n_steps, (
            f"weather trace too short: {wb.shape[0]} < {cfg.n_steps}")
        wb = wb[: cfg.n_steps]
    price_policy = cfg.battery.enabled and cfg.battery.policy != "carbon"
    if price_policy and not cfg.pricing.enabled:
        raise ValueError(
            f"battery dispatch policy '{cfg.battery.policy}' arbitrages the "
            "price trace but cfg.pricing.enabled is False: enable the "
            "pricing subsystem (core/pricing.py)")
    if cfg.pricing.enabled:
        pr = dyn.get("price_trace")
        if pr is None:  # traceless: the legacy flat tariff, now simulated
            pr = jnp.full_like(ci, cfg.pricing.flat_price_per_kwh)
        else:
            pr = jnp.asarray(pr, jnp.float32)
            assert pr.shape[0] >= cfg.n_steps, (
                f"price trace too short: {pr.shape[0]} < {cfg.n_steps}")
            pr = pr[: cfg.n_steps]
        if price_policy:
            plo, phi = pricing_mod.precompute_price_signals(pr, cfg.dt_h,
                                                            cfg.battery)
        else:
            plo = phi = jnp.zeros_like(ci)
    else:
        pr = plo = phi = jnp.zeros_like(ci)
    cf = dyn.get("pv_cf_trace")
    if cfg.renewables.enabled:
        if cf is None:  # plant declared but no resource data: dark panels
            cf = jnp.zeros_like(ci)
        else:
            cf = jnp.asarray(cf, jnp.float32)
            assert cf.shape[0] >= cfg.n_steps, (
                f"pv trace too short: {cf.shape[0]} < {cfg.n_steps}")
            cf = cf[: cfg.n_steps]
    else:
        if cf is not None:
            raise ValueError(
                "a pv_cf_trace was provided but cfg.renewables.enabled is "
                "False: the PV trace would be silently ignored — enable the "
                "renewables subsystem (core/renewables.py)")
        cf = jnp.zeros_like(ci)
    if cfg.resilience.enabled:
        derate, pdu_down = resilience_mod.facility_failure_series(
            dyn.get("seed", cfg.seed), cfg.n_steps, cfg.dt_h, cfg.resilience,
            hazard_scale=dyn.get("failure_hazard_scale"))
        cap = dyn.get("pdu_cap_kw")
        cap = (jnp.float32(cfg.resilience.pdu_cap_kw) if cap is None
               else jnp.asarray(cap, jnp.float32))
        pdu_cap = jnp.where(pdu_down, cap, jnp.float32(jnp.inf))
    else:  # inert placeholders: no stage reads them, so XLA drops them
        derate = jnp.ones_like(ci)
        pdu_cap = jnp.full_like(ci, jnp.inf)
    return StepInputs(ci=ci, batt_threshold=bt, ci_rising=rising,
                      shift_threshold=st, wet_bulb_c=wb, price=pr,
                      price_lo=plo, price_hi=phi, pv_cf=cf,
                      chiller_derate=derate, pdu_cap_kw=pdu_cap)


# --------------------------------------------------------------------------
# stages
# --------------------------------------------------------------------------

def stage_failures(cfg: SimConfig) -> Stage:
    resil = cfg.resilience.enabled
    heat_mult = cfg.resilience.heat_hazard_mult

    def fn(state: SimState, ctx: dict):
        hazard = None
        if resil:  # failure_hazard_scale dyn + heat-correlated failures
            hz = ctx.get("failure_hazard_scale")
            hazard = (jnp.float32(1.0) if hz is None
                      else jnp.asarray(hz, jnp.float32))
            if heat_mult > 0.0:  # a derated chiller cooks the hosts
                hazard = hazard * (1.0 + heat_mult
                                   * (1.0 - ctx["chiller_derate"]))
        rng, hosts, newly_down = failures_mod.step_host_failures(
            state.rng, state.hosts, state.t, cfg.dt_h, cfg.failures,
            hazard=hazard)
        tasks, n_int = failures_mod.interrupt_tasks(state.tasks, newly_down,
                                                    cfg.failures)
        metrics = state.metrics._replace(
            n_interrupts=state.metrics.n_interrupts + n_int)
        return state._replace(rng=rng, hosts=hosts, tasks=tasks,
                              metrics=metrics), ctx
    return fn


def stage_checkpoint(cfg: SimConfig) -> Stage:
    # static: one host-side divide, not a float boundary test in the scan
    isteps = failures_mod.checkpoint_interval_steps(cfg.failures, cfg.dt_h)

    def fn(state: SimState, ctx: dict):
        tasks = failures_mod.checkpoint_tick(state.tasks, state.step, isteps,
                                             cfg.failures)
        return state._replace(tasks=tasks), ctx
    return fn


def stage_task_stopper(cfg: SimConfig) -> Stage:
    def fn(state: SimState, ctx: dict):
        tasks = state.tasks
        stop = shifting_mod.should_stop(ctx["ci"], ctx["shift_threshold"],
                                        state.t, tasks.arrival, cfg.shifting,
                                        shiftable=tasks.shiftable)
        stop = stop & (tasks.status == RUNNING)
        n = jnp.sum(stop.astype(jnp.float32))
        tasks = tasks._replace(
            status=jnp.where(stop, PENDING, tasks.status).astype(jnp.int32),
            host=jnp.where(stop, -1, tasks.host).astype(jnp.int32))
        # graceful pauses are NOT failure interrupts: they roll back no work
        # and cost no checkpoint restore, so they get their own counter —
        # conflating them into n_interrupts double-counted resilience stats
        metrics = state.metrics._replace(
            n_stops=state.metrics.n_stops + n)
        return state._replace(tasks=tasks, metrics=metrics), ctx
    return fn


def _presort_enabled(cfg: SimConfig) -> bool:
    """True when `simulate` permutes the task table into (priority desc,
    arrival) row order before the scan (see state.priority_schedule_order)
    — the scheduler stage must then run its presorted FIFO-prefix path.
    Static in cfg, so the stage closure and `simulate` always agree."""
    return cfg.scheduler.priority_levels > 1 and cfg.scheduler.mode == "first_fit"


def stage_scheduler(cfg: SimConfig) -> Stage:
    reactive = cfg.resilience.enabled and cfg.resilience.reactive_placement
    presorted = _presort_enabled(cfg)

    def fn(state: SimState, ctx: dict):
        shift_ok = shifting_mod.start_allowed(
            ctx["ci"], ctx["shift_threshold"], state.t, state.tasks.arrival,
            cfg.shifting, shiftable=state.tasks.shiftable)
        n_delayed = jnp.sum(
            ((state.tasks.status == PENDING) & (state.tasks.arrival <= state.t)
             & ~shift_ok).astype(jnp.float32))
        order = (resilience_mod.host_rank(state.hosts, state.t)
                 if reactive else None)
        tasks, iters, placed, bound = scheduler_mod.schedule_step(
            state.tasks, state.hosts, state.t, shift_ok, cfg.scheduler,
            slots=ctx.get("slots_per_step"), host_order=order,
            presorted=presorted)
        m = state.metrics
        metrics = m._replace(n_shift_delays=m.n_shift_delays + n_delayed,
                             first_fit_iters=m.first_fit_iters + iters,
                             first_fit_placed=m.first_fit_placed + placed,
                             slot_bound_steps=m.slot_bound_steps + bound)
        return state._replace(tasks=tasks, metrics=metrics), ctx
    return fn


def stage_progress(cfg: SimConfig) -> Stage:
    resil = cfg.resilience.enabled

    def fn(state: SimState, ctx: dict):
        tasks = state.tasks
        running = tasks.status == RUNNING
        # straggler hosts advance work at speed < 1: the speed of each
        # task's host, copied to the row when the scheduler placed it
        speed = tasks.speed
        if resil:  # thermal throttle computed from the PREVIOUS step
            speed = speed * state.throttle
        advance = cfg.dt_h * jnp.where(running, speed, 1.0)
        done_now = running & (tasks.remaining <= advance)
        finish = jnp.where(done_now,
                           state.t + tasks.remaining / jnp.maximum(speed, 1e-6),
                           tasks.finish)
        remaining = jnp.where(running, jnp.maximum(tasks.remaining - advance, 0.0),
                              tasks.remaining)
        tasks = tasks._replace(
            remaining=remaining,
            finish=finish,
            status=jnp.where(done_now, DONE, tasks.status).astype(jnp.int32),
            host=jnp.where(done_now, -1, tasks.host).astype(jnp.int32))
        return state._replace(tasks=tasks), ctx
    return fn


def stage_it_power(cfg: SimConfig) -> Stage:
    """IT power: per-host utilization, per-host power (the Pallas kernel
    with `cfg.use_pallas`), and their sum.  Writes `flow.it_kw` (and
    provisionally `flow.grid_import_kw`: with no later facility stage, the
    IT draw IS the metered import).

    With resilience on, the previous step's thermal throttle caps host
    utilization and the PDU failure process clamps the summed IT draw
    (`flow.it_kw` is the CAPPED value every downstream consumer meters;
    the raw demand is kept in ctx `raw_it_kw` for the next-throttle rule).
    `stage_power` runs it on the stage pipeline, the megakernel's demand
    step on its own."""
    resil = cfg.resilience.enabled

    def fn(state: SimState, ctx: dict):
        cpu_u, gpu_u = scheduler_mod.host_utilization(state.tasks, state.hosts)
        if resil:  # thermal throttle computed from the PREVIOUS step
            cpu_u = cpu_u * state.throttle
            gpu_u = gpu_u * state.throttle
        on = (state.hosts.active & state.hosts.up).astype(jnp.float32)
        if cfg.use_pallas:
            from repro.kernels import ops as pc_ops
            p = pc_ops.host_power(cpu_u, gpu_u, state.hosts.n_gpus, on,
                                  cfg.cpu_power, cfg.gpu_power)
        else:
            p = host_power_kw(cpu_u, gpu_u, state.hosts.n_gpus, on,
                              cfg.cpu_power, cfg.gpu_power)
        it_kw = jnp.sum(p)
        if resil:
            ctx["raw_it_kw"] = it_kw  # pre-clamp demand (next-throttle rule)
            it_kw = jnp.minimum(it_kw, ctx["pdu_cap_kw"])
        flow = ctx["flow"]._replace(it_kw=it_kw, grid_import_kw=it_kw)
        ctx = dict(ctx, flow=flow, host_power_kw=p,
                   host_cpu_util=cpu_u, host_gpu_util=gpu_u)
        return state, ctx
    return fn


def _max_overcommit(state: SimState) -> jax.Array:
    """Largest overcommit of any host's cores or GPUs: a capacity-invariant
    probe for tests and debugging (`collect_series`)."""
    free_c, free_g = scheduler_mod.free_capacity(state.tasks, state.hosts)
    return jnp.maximum(jnp.max(-free_c), jnp.max(-free_g))


def stage_power(cfg: SimConfig) -> Stage:
    """`stage_it_power`, or with Pallas and cooling on and resilience off,
    one fused kernel that also computes the cooling draw (`stage_cooling`
    reads it from ctx).  Under `collect_series` it also records
    `_max_overcommit`."""
    fused = (cfg.use_pallas and cfg.cooling.enabled
             and not cfg.resilience.enabled)
    it_power = stage_it_power(cfg)

    def fn(state: SimState, ctx: dict):
        if cfg.collect_series:
            ctx["max_overcommit"] = _max_overcommit(state)
        if not fused:
            return it_power(state, ctx)
        # one VMEM pass: per-host power + IT sum + cooling + water.  (not
        # with resilience: the PDU clamp sits between the IT sum and the
        # cooling model, splitting the fused op in two)
        from repro.kernels import ops as pc_ops
        cpu_u, gpu_u = scheduler_mod.host_utilization(state.tasks, state.hosts)
        on = (state.hosts.active & state.hosts.up).astype(jnp.float32)
        sp = ctx.get("cooling_setpoint", cfg.cooling.setpoint_c)
        p, it_kw, cool_kw, water = pc_ops.facility_power(
            cpu_u, gpu_u, state.hosts.n_gpus, on, ctx["wet_bulb_c"],
            sp, cfg.cpu_power, cfg.gpu_power, cfg.cooling)
        flow = ctx["flow"]._replace(it_kw=it_kw, grid_import_kw=it_kw)
        ctx = dict(ctx, flow=flow, host_power_kw=p,
                   host_cpu_util=cpu_u, host_gpu_util=gpu_u,
                   fused_cooling_kw=cool_kw, fused_water_l_per_h=water)
        return state, ctx
    return fn


def stage_cooling(cfg: SimConfig) -> Stage:
    """IT power -> facility power: writes `flow.cooling_kw` and lifts
    `flow.grid_import_kw` to the facility draw.

    Sits between `stage_power` and `stage_battery` so downstream stages
    (battery peak-shaving, carbon accounting, peak-power tracking) see the
    facility draw.  `cooling_setpoint` may be a traced dyn value (grid axis).
    With `heat_reuse_fraction > 0`, that share of the chiller-path heat is
    reclaimed for district heating before the tower: it accumulates in
    `metrics.heat_reuse` and stops evaporating water (dry heat exchangers).
    """
    reuse = cfg.cooling.heat_reuse_fraction
    resil = cfg.resilience.enabled

    def fn(state: SimState, ctx: dict):
        flow = ctx["flow"]
        it_kw = flow.it_kw
        # None (not 1.0) when resilience is off: the derated expressions
        # reassociate and would not be bitwise-identical to the healthy path
        derate = ctx["chiller_derate"] if resil else None
        if "fused_cooling_kw" in ctx:   # Pallas path: computed in stage_power
            cooling_kw = ctx["fused_cooling_kw"]
            water_l_per_h = ctx["fused_water_l_per_h"]
        else:
            cooling_kw, water_l_per_h = thermal_mod.cooling_step(
                it_kw, ctx["wet_bulb_c"], cfg.cooling,
                setpoint_c=ctx.get("cooling_setpoint"),
                chiller_derate=derate)
        m = state.metrics
        if reuse > 0.0:
            heat_kw = thermal_mod.reclaimable_heat_kw(
                it_kw, cooling_kw, ctx["wet_bulb_c"], cfg.cooling,
                setpoint_c=ctx.get("cooling_setpoint"),
                chiller_derate=derate)
            water_l_per_h = water_l_per_h * (1.0 - reuse)
            m = m._replace(heat_reuse=m.heat_reuse + reuse * heat_kw * cfg.dt_h)
        metrics = m._replace(
            cooling_energy=m.cooling_energy + cooling_kw * cfg.dt_h,
            water_l=m.water_l + water_l_per_h * cfg.dt_h)
        flow = flow._replace(cooling_kw=cooling_kw,
                             grid_import_kw=it_kw + cooling_kw)
        return state._replace(metrics=metrics), dict(ctx, flow=flow)
    return fn


def stage_renewables(cfg: SimConfig) -> Stage:
    """On-site PV supply: writes `flow.pv_kw` from the capacity-factor
    input and the (possibly traced) `pv_capacity_kw`.  Netting against the
    facility load happens downstream — in `stage_battery` (so the battery
    dispatches on the net load and charges from surplus) or, without a
    battery, in `stage_net_meter`."""
    def fn(state: SimState, ctx: dict):
        cap = ctx.get("pv_capacity_kw")
        if cap is None:
            cap = jnp.float32(cfg.renewables.pv_capacity_kw)
        pv_kw = renewables_mod.pv_power_kw(cap, ctx["pv_cf"])
        return state, dict(ctx, flow=ctx["flow"]._replace(pv_kw=pv_kw))
    return fn


def stage_net_meter(cfg: SimConfig) -> Stage:
    """Settle the ledger when renewables run WITHOUT a battery: PV serves
    the facility load, and the storage-less surplus is exported or
    curtailed per `cfg.renewables.export_allowed`."""
    def fn(state: SimState, ctx: dict):
        flow = ctx["flow"]
        load = flow.it_kw + flow.cooling_kw
        net_load, surplus = renewables_mod.net_load_split(load, flow.pv_kw)
        _, export_kw, curtailed_kw = renewables_mod.split_surplus(
            surplus, jnp.zeros_like(surplus), cfg.renewables)
        flow = flow._replace(grid_import_kw=net_load,
                             grid_export_kw=export_kw,
                             curtailed_kw=curtailed_kw)
        return state, dict(ctx, flow=flow)
    return fn


def stage_battery(cfg: SimConfig) -> Stage:
    """Storage dispatch in ledger terms: writes `flow.batt_charge_kw` /
    `flow.batt_discharge_kw` and settles `flow.grid_import_kw` (and, with
    renewables on, `grid_export_kw`/`curtailed_kw` — surplus PV charges
    the battery before anything is exported or thrown away)."""
    renew = cfg.renewables.enabled

    def fn(state: SimState, ctx: dict):
        flow = ctx["flow"]
        load = flow.it_kw + flow.cooling_kw
        if renew:
            net_load, surplus = renewables_mod.net_load_split(load, flow.pv_kw)
        else:
            net_load, surplus = load, None
        batt, charge_kw, discharge_kw = battery_mod.battery_flow_step(
            state.battery, net_load, ctx["ci"], ctx["batt_threshold"],
            ctx["ci_rising"], cfg.dt_h, cfg.battery,
            capacity_kwh=ctx.get("batt_capacity_kwh"),
            rate_kw=ctx.get("batt_rate_kw"),
            price=ctx.get("price"), price_lo=ctx.get("price_lo"),
            price_hi=ctx.get("price_hi"),
            dispatch_lambda=ctx.get("dispatch_lambda"),
            pv_surplus_kw=surplus)
        if renew:
            pv_to_batt, export_kw, curtailed_kw = renewables_mod.split_surplus(
                surplus, charge_kw, cfg.renewables)
            grid_charge_kw = charge_kw - pv_to_batt
            flow = flow._replace(
                batt_charge_kw=charge_kw, batt_discharge_kw=discharge_kw,
                grid_import_kw=net_load + grid_charge_kw - discharge_kw,
                grid_export_kw=export_kw, curtailed_kw=curtailed_kw)
        else:
            # the supply-free ledger: import = facility + charge - discharge
            # (exactly the pre-ledger metered-grid expression)
            flow = flow._replace(
                batt_charge_kw=charge_kw, batt_discharge_kw=discharge_kw,
                grid_import_kw=load + charge_kw - discharge_kw)
        metrics = state.metrics._replace(
            batt_discharged=state.metrics.batt_discharged
            + discharge_kw * cfg.dt_h)
        return state._replace(battery=batt, metrics=metrics), dict(ctx,
                                                                   flow=flow)
    return fn


def stage_pricing(cfg: SimConfig) -> Stage:
    """Grid flows -> money: energy charge + billing-window demand charge on
    `flow.grid_import_kw`, minus the export-tariff revenue earned by
    `flow.grid_export_kw` (core/pricing.export_revenue_step).

    Sits after `stage_battery` so the bill meters the battery-shaped grid
    draw (charge spikes cost, shaved peaks save) — the same quantity
    `peak_power` tracks.  The price may vary per step (`price_trace` dyn
    key / `price_axis` grid axis); the final open billing window is settled
    by `summarize`.
    """
    wsteps = pricing_mod.billing_window_steps(cfg.pricing, cfg.dt_h)
    renew = cfg.renewables.enabled

    def fn(state: SimState, ctx: dict):
        flow = ctx["flow"]
        m = state.metrics
        ec, dc, wp = pricing_mod.pricing_step(
            m.energy_cost, m.demand_cost, m.window_peak_kw,
            flow.grid_import_kw, ctx["price"], state.step, cfg.dt_h, wsteps,
            cfg.pricing.demand_charge_per_kw)
        metrics = m._replace(energy_cost=ec, demand_cost=dc,
                             window_peak_kw=wp)
        if renew:
            metrics = metrics._replace(
                export_revenue=pricing_mod.export_revenue_step(
                    m.export_revenue, flow.grid_export_kw, ctx["price"],
                    cfg.dt_h, cfg.pricing))
        return state._replace(metrics=metrics), ctx
    return fn


def _batt_embodied_rate(cfg: SimConfig, capacity_kwh=None):
    """Battery embodied carbon per hour of ownership, kg/h: for the traced
    `batt_capacity_kwh` where one is given, else for the configured size."""
    if capacity_kwh is None or not cfg.battery.enabled:
        return battery_mod.battery_embodied_rate_kg_per_h(cfg.battery)
    return (capacity_kwh * cfg.battery.embodied_kg_per_kwh
            / (cfg.battery.lifetime_years * HOURS_PER_YEAR))


def stage_carbon(cfg: SimConfig) -> Stage:
    """Carbon + energy accounting off the settled ledger: operational
    carbon, grid energy and the tracked peak all meter
    `flow.grid_import_kw` — with renewables on, the NET import (on-site
    generation displaces carbon one-for-one; exports earn no credit under
    location-based accounting)."""
    renew = cfg.renewables.enabled

    def fn(state: SimState, ctx: dict):
        flow = ctx["flow"]
        grid_kw = flow.grid_import_kw
        n_active = jnp.sum(state.hosts.active.astype(jnp.float32))
        batt_rate = _batt_embodied_rate(cfg, ctx.get("batt_capacity_kwh"))
        op, emb = carbon_mod.carbon_delta(grid_kw, ctx["ci"], cfg.dt_h,
                                          n_active, cfg.embodied, batt_rate)
        m = state.metrics
        metrics = m._replace(
            op_carbon=m.op_carbon + op,
            emb_carbon=m.emb_carbon + emb,
            grid_energy=m.grid_energy + grid_kw * cfg.dt_h,
            dc_energy=m.dc_energy + (flow.it_kw + flow.cooling_kw) * cfg.dt_h,
            it_energy=m.it_energy + flow.it_kw * cfg.dt_h,
            peak_power=jnp.maximum(m.peak_power, grid_kw))
        if renew:
            metrics = metrics._replace(
                pv_energy=metrics.pv_energy + flow.pv_kw * cfg.dt_h,
                export_energy=(metrics.export_energy
                               + flow.grid_export_kw * cfg.dt_h),
                curtailed_energy=(metrics.curtailed_energy
                                  + flow.curtailed_kw * cfg.dt_h))
        return state._replace(metrics=metrics), ctx
    return fn


def stage_resilience(cfg: SimConfig) -> Stage:
    """Close the thermal loop: from this step's SETTLED facility state,
    compute the throttle the NEXT step will run under (one-step delay =
    causal recurrence; see core/resilience.next_throttle), and account the
    resilience metrics (hours throttled / hours with facility equipment
    derated).  Runs once the IT draw is settled (last on the stage
    pipeline, right after `stage_it_power` in the megakernel's demand step),
    so it sees the capped `flow.it_kw`."""
    rcfg = cfg.resilience
    dt = jnp.float32(cfg.dt_h)

    def fn(state: SimState, ctx: dict):
        flow: EnergyFlow = ctx["flow"]
        derate, cap = ctx["chiller_derate"], ctx["pdu_cap_kw"]
        m = state.metrics
        m = m._replace(
            throttled_h=m.throttled_h
            + dt * (state.throttle < 1.0).astype(jnp.float32),
            derate_h=m.derate_h
            + dt * ((derate < 1.0) | jnp.isfinite(cap)).astype(jnp.float32))
        throttle = resilience_mod.next_throttle(
            flow.it_kw, ctx["raw_it_kw"], ctx["wet_bulb_c"], derate, cap,
            rcfg, threshold_c=ctx.get("throttle_inlet_c"))
        # the throttle this step RAN under (stage_progress/stage_power read
        # state.throttle before this stage replaces it) — stashed for the
        # probe bus, which samples after the recurrence has advanced
        ctx["throttle_factor"] = state.throttle
        return state._replace(metrics=m, throttle=throttle), ctx
    return fn


def demand_stages(cfg: SimConfig) -> list[Stage]:
    """The recurrent head of every step: checkpoint -> failures -> task
    stopper -> scheduler -> progress, each where its technique is on.
    `default_pipeline` starts with it; the megakernel's demand scan runs
    it alone."""
    stages: list[Stage] = []
    if cfg.failures.enabled:
        # checkpoint BEFORE failures: the boundary snapshot at time t must
        # capture all work completed by t, including the previous step's
        # progress — otherwise a failure in the same step rolls back past
        # its own checkpoint and per-step checkpointing still loses work
        # (tests/test_resilience.py pins lost_work == 0 at interval == dt)
        if cfg.failures.checkpointing:
            stages.append(stage_checkpoint(cfg))
        stages.append(stage_failures(cfg))
    if cfg.shifting.enabled and cfg.shifting.stop_running:
        stages.append(stage_task_stopper(cfg))
    return stages + [stage_scheduler(cfg), stage_progress(cfg)]


def default_pipeline(cfg: SimConfig) -> list[Stage]:
    """Technique composition: each enabled technique contributes its stage.

    Mirrors paper Fig 4 — adding the task stopper or the battery touches only
    its own stage; everything else is unchanged.
    """
    stages = demand_stages(cfg) + [stage_power(cfg)]
    if cfg.cooling.enabled:
        stages.append(stage_cooling(cfg))
    if cfg.renewables.enabled:
        stages.append(stage_renewables(cfg))
    if cfg.battery.enabled:
        stages.append(stage_battery(cfg))
    elif cfg.renewables.enabled:
        stages.append(stage_net_meter(cfg))
    if cfg.pricing.enabled:
        stages.append(stage_pricing(cfg))
    stages.append(stage_carbon(cfg))
    if cfg.resilience.enabled:
        stages.append(stage_resilience(cfg))
    return stages


# --------------------------------------------------------------------------
# executor
# --------------------------------------------------------------------------

def _advance_clock(state: SimState, cfg: SimConfig) -> SimState:
    """End-of-step clock tick: t is DERIVED from the step index, never
    accumulated.  Accumulating `t += dt_h` compounds one f32 rounding per
    step — at dt_h = 0.1 that is ~0.15 h of drift over 12 000 steps,
    silently shifting SLA deadlines and every time-derived boundary.  The
    product form carries a single rounding regardless of horizon
    (tests/test_simclock.py)."""
    step1 = state.step + 1
    return state._replace(t=step1.astype(jnp.float32) * jnp.float32(cfg.dt_h),
                          step=step1)


def _queue_depth(state: SimState) -> jax.Array:
    """Arrived-but-pending task count at the state's current time."""
    return jnp.sum(((state.tasks.status == PENDING)
                    & (state.tasks.arrival <= state.t)).astype(jnp.float32))


def stage_probes(cfg: SimConfig) -> Stage:
    """Probe-bus sampler (cfg.probes): runs after every other stage, so it
    sees the SETTLED ledger plus post-dispatch SoC and the post-pricing
    running window peak.  Samples use the pre-increment `state.step`/`t`
    of the step being executed."""
    stride = max(int(cfg.probes.stride), 1)

    def fn(state: SimState, ctx: dict):
        flow: EnergyFlow = ctx["flow"]
        sample = {f: getattr(flow, f) for f in EnergyFlow._fields}
        sample["soc_kwh"] = state.battery.charge
        sample["window_peak_kw"] = state.metrics.window_peak_kw
        sample["queue_depth"] = _queue_depth(state)
        # resilience channels: applied throttle / derate / PDU cap — the
        # ctx carries 1.0 / 1.0 / +inf series when resilience is off, so
        # the channels exist (and agree across backends) unconditionally
        sample["throttle_factor"] = ctx.get("throttle_factor",
                                            jnp.float32(1.0))
        sample["chiller_derate"] = ctx["chiller_derate"]
        sample["pdu_cap_kw"] = ctx["pdu_cap_kw"]
        probes = telemetry_mod.probe_write(state.probes, state.step,
                                           stride, sample)
        return state._replace(probes=probes), ctx
    return fn


def _stage_label(stage: Stage) -> str:
    """'stage_power.<locals>.fn' -> 'stage_power' for span/scope names."""
    q = getattr(stage, "__qualname__", "")
    return q.split(".<locals>")[0] or getattr(stage, "__name__", "stage")


def step_ctx(inputs: StepInputs, dyn: dict) -> dict:
    """A step's ctx: its exogenous inputs under their `StepInputs` field
    names, an empty energy-flow ledger, and the traced `dyn` values."""
    return {**inputs._asdict(), "flow": init_energy_flow(), **dyn}


def _run_stages(stages: Sequence[Stage], state: SimState, ctx: dict):
    """Run `stages` in order, each under its own stage scope."""
    for stage in stages:
        with telemetry_mod.stage_scope(_stage_label(stage)):
            state, ctx = stage(state, ctx)
    return state, ctx


def _series(cfg: SimConfig, flow: EnergyFlow, ci, n_running, soc,
            max_overcommit, wet_bulb_c, price) -> dict:
    """The `collect_series` outputs, from one step's values (stage
    pipeline) or from [S] series (megakernel)."""
    ys = {"grid_power_kw": flow.grid_import_kw,
          "dc_power_kw": flow.it_kw + flow.cooling_kw,
          "ci": ci, "n_running": n_running, "battery_charge": soc,
          "max_overcommit": max_overcommit, "flow": flow}
    if cfg.cooling.enabled:
        ys["cooling_power_kw"] = flow.cooling_kw
        ys["wet_bulb_c"] = wet_bulb_c
    if cfg.pricing.enabled:
        ys["price_per_kwh"] = price
    return ys


def build_step_fn(cfg: SimConfig, stages: Sequence[Stage] | None = None,
                  dyn: dict | None = None):
    """The stage pipeline's scan step `(state, StepInputs) -> (state, ys)`:
    `stages` (default: `default_pipeline`, plus the probe sampler with
    probes on) over `step_ctx`, then the clock tick; `ys` is `_series`
    under `collect_series`, else None."""
    stages = default_pipeline(cfg) if stages is None else list(stages)
    if cfg.probes.enabled:
        stages.append(stage_probes(cfg))
    dyn = dyn or {}

    def step(state: SimState, inputs: StepInputs):
        state, ctx = _run_stages(stages, state, step_ctx(inputs, dyn))
        state = _advance_clock(state, cfg)
        if not cfg.collect_series:
            return state, None
        n_running = jnp.sum((state.tasks.status == RUNNING).astype(jnp.int32))
        return state, _series(
            cfg, ctx["flow"], ctx["ci"], n_running, state.battery.charge,
            ctx.get("max_overcommit", jnp.float32(0.0)), ctx["wet_bulb_c"],
            ctx["price"])

    return step


# --------------------------------------------------------------------------
# megakernel backend (docstring: "Kernel backends")
# --------------------------------------------------------------------------

def _build_demand_step(cfg: SimConfig, dyn: dict):
    """Scan step for the megakernel DEMAND phase: `demand_stages`, then
    `stage_it_power` and, with resilience on, `stage_resilience` — the
    stage pipeline's own stage functions in its order, so the emitted
    `it_kw[S]` (the capped draw, the only demand->facility coupling)
    matches it and the facility half stays vectorized.  Also emits, with
    probes on, the queue depth and the throttle each step ran under, and
    under `collect_series` the capacity/occupancy probes the stage-pipeline
    series carry.

    Its xs are a `StepInputs` holding only the series these stages read
    (`_simulate_megakernel`); the others are None, so their ctx keys are
    None too."""
    stages = demand_stages(cfg) + [stage_it_power(cfg)]
    if cfg.resilience.enabled:
        stages.append(stage_resilience(cfg))

    def step(state: SimState, inputs: StepInputs):
        state, ctx = _run_stages(stages, state, step_ctx(inputs, dyn))
        # probe-bus queue depth samples the pre-increment time, exactly like
        # the stage pipeline's probe stage (which runs before the increment)
        qd = _queue_depth(state) if cfg.probes.enabled else None
        state = _advance_clock(state, cfg)
        ys = {"it_kw": ctx["flow"].it_kw}
        if qd is not None:
            ys["queue_depth"] = qd
            ys["throttle_factor"] = ctx.get("throttle_factor",
                                            jnp.float32(1.0))
        if cfg.collect_series:
            ys["max_overcommit"] = _max_overcommit(state)
            ys["n_running"] = jnp.sum((state.tasks.status == RUNNING)
                                      .astype(jnp.int32))
        return state, ys

    return step


def facility_totals_from_flows(flows: dict, inputs: StepInputs,
                               cfg: SimConfig) -> dict:
    """Reduce the [S] flow series of `ref.fused_facility_chain` to the
    per-run totals the metrics accumulator needs.  The Pallas megakernel
    (kernels/fused_step.py) produces this SAME dict from per-block partial
    sums, which is what makes the two facility paths interchangeable."""
    dt = jnp.float32(cfg.dt_h)
    grid = flows["grid_import_kw"]
    load = flows["it_kw"] + flows["cooling_kw"]
    totals = {
        "op_carbon": jnp.sum(grid * inputs.ci) * dt / 1000.0,
        "grid_energy": jnp.sum(grid) * dt,
        "dc_energy": jnp.sum(load) * dt,
        "it_energy": jnp.sum(flows["it_kw"]) * dt,
        "peak_power": jnp.max(grid),
        "batt_discharged": jnp.sum(flows["batt_discharge_kw"]) * dt,
        "cooling_energy": jnp.sum(flows["cooling_kw"]) * dt,
        "water_l": jnp.sum(flows["water_l_per_h"]) * dt,
        "heat_reuse": jnp.sum(flows["heat_reuse_kw"]) * dt,
        "pv_energy": jnp.sum(flows["pv_kw"]) * dt,
        "export_energy": jnp.sum(flows["grid_export_kw"]) * dt,
        "curtailed_energy": jnp.sum(flows["curtailed_kw"]) * dt,
        "soc_final": flows["soc"][-1],
        "was_charging": flows["want_charge"][-1],
    }
    if cfg.pricing.enabled:
        wsteps = pricing_mod.billing_window_steps(cfg.pricing, cfg.dt_h)
        s = grid.shape[0]
        n_win = -(-s // wsteps)
        padded = jnp.concatenate(
            [grid, jnp.zeros(n_win * wsteps - s, grid.dtype)])
        # windows [0,w), [w,2w), ...: the stage pipeline closes a window at
        # step i = w, 2w, ... and `summarize` settles the final OPEN one —
        # so closed-window peaks bill here, the last peak stays running
        peaks = jnp.max(padded.reshape(n_win, wsteps), axis=1)
        totals["energy_cost"] = jnp.sum(grid * inputs.price) * dt
        totals["demand_cost"] = (jnp.sum(peaks[:-1])
                                 * jnp.float32(cfg.pricing.demand_charge_per_kw))
        totals["window_peak_kw"] = peaks[-1]
        if cfg.renewables.enabled:
            totals["export_revenue"] = (
                jnp.sum(flows["grid_export_kw"] * inputs.price) * dt
                * jnp.float32(cfg.pricing.export_price_fraction))
    return totals


def _merge_facility_totals(state: SimState, totals: dict, cfg: SimConfig,
                           dyn: dict) -> SimState:
    """Fold facility-phase totals (+ the closed-form embodied integral) into
    the demand-phase final state."""
    m = state.metrics
    dt = cfg.dt_h
    # embodied carbon is load-independent and `hosts.active` never changes
    # during a run (failures toggle `up`), so the per-step accumulation is a
    # closed-form product — the one stage_carbon term with no flow input
    n_active = jnp.sum(state.hosts.active.astype(jnp.float32))
    batt_rate = _batt_embodied_rate(cfg, dyn.get("batt_capacity_kwh"))
    host_rate = carbon_mod.host_embodied_rate_kg_per_h(cfg.embodied)
    emb = (n_active * host_rate + batt_rate) * dt * cfg.n_steps
    m = m._replace(
        op_carbon=m.op_carbon + totals["op_carbon"],
        emb_carbon=m.emb_carbon + jnp.float32(emb),
        grid_energy=m.grid_energy + totals["grid_energy"],
        dc_energy=m.dc_energy + totals["dc_energy"],
        it_energy=m.it_energy + totals["it_energy"],
        peak_power=jnp.maximum(m.peak_power, totals["peak_power"]),
        batt_discharged=m.batt_discharged + totals["batt_discharged"])
    if cfg.cooling.enabled:
        m = m._replace(
            cooling_energy=m.cooling_energy + totals["cooling_energy"],
            water_l=m.water_l + totals["water_l"],
            heat_reuse=m.heat_reuse + totals["heat_reuse"])
    if cfg.renewables.enabled:
        m = m._replace(
            pv_energy=m.pv_energy + totals["pv_energy"],
            export_energy=m.export_energy + totals["export_energy"],
            curtailed_energy=m.curtailed_energy + totals["curtailed_energy"])
    if cfg.pricing.enabled:
        m = m._replace(
            energy_cost=m.energy_cost + totals["energy_cost"],
            demand_cost=m.demand_cost + totals["demand_cost"],
            window_peak_kw=jnp.maximum(m.window_peak_kw,
                                       totals["window_peak_kw"]))
        if cfg.renewables.enabled:
            m = m._replace(export_revenue=m.export_revenue
                           + totals["export_revenue"])
    battery = BatteryState(charge=totals["soc_final"],
                           was_charging=totals["was_charging"])
    return state._replace(metrics=m, battery=battery)


def _simulate_megakernel(state0: SimState, inputs: StepInputs,
                         cfg: SimConfig, dyn: dict):
    from repro.kernels import ref as ref_mod  # lazy: kernels import core

    step = _build_demand_step(cfg, dyn)
    # only the series the demand stages read ride the scan: the shifting
    # gate reads the CI trace, the throttle recurrence the facility series.
    # With neither on the scan has no batched input, so a scenario vmap
    # hoists it.
    carried = set()
    if cfg.shifting.enabled:
        carried |= {"ci", "shift_threshold"}
    if cfg.resilience.enabled:
        carried |= {"wet_bulb_c", "chiller_derate", "pdu_cap_kw"}
    xs = inputs._replace(**{f: None for f in StepInputs._fields
                            if f not in carried})
    with telemetry_mod.stage_scope("megakernel.demand"):
        final, demand_ys = jax.lax.scan(step, state0, xs,
                                        length=cfg.n_steps)
    it_series = demand_ys["it_kw"]

    chain_kwargs = dict(
        soc0=0.0, setpoint_c=dyn.get("cooling_setpoint"),
        batt_capacity_kwh=dyn.get("batt_capacity_kwh"),
        batt_rate_kw=dyn.get("batt_rate_kw"),
        dispatch_lambda=dyn.get("dispatch_lambda"),
        pv_capacity_kw=dyn.get("pv_capacity_kw"))
    if cfg.resilience.enabled:
        chain_kwargs["chiller_derate"] = inputs.chiller_derate
    # the probe bus needs the per-step flow series, so (like collect_series)
    # it routes the facility phase through the reference chain rather than
    # the totals-only Pallas kernel — probing is opt-in observability;
    # resilience also takes the reference chain (the fused kernel's quantized
    # trace store has no slot for the derate series)
    if (cfg.use_pallas and not cfg.collect_series and not cfg.probes.enabled
            and not cfg.resilience.enabled):
        from repro.kernels import ops as pc_ops
        with telemetry_mod.stage_scope("megakernel.facility"):
            totals = pc_ops.facility_totals(
                it_series, inputs.ci, inputs.wet_bulb_c, inputs.price,
                inputs.price_lo, inputs.price_hi, inputs.pv_cf,
                inputs.batt_threshold, inputs.ci_rising, cfg,
                trace_store=cfg.trace_store, **chain_kwargs)
        final = _merge_facility_totals(final, totals, cfg, dyn)
        return final, None
    with telemetry_mod.stage_scope("megakernel.facility"):
        flows = ref_mod.fused_facility_chain(
            it_series, inputs.ci, inputs.wet_bulb_c, inputs.price,
            inputs.price_lo, inputs.price_hi, inputs.pv_cf,
            inputs.batt_threshold, inputs.ci_rising, cfg.dt_h, cfg,
            **chain_kwargs)
        totals = facility_totals_from_flows(flows, inputs, cfg)
    final = _merge_facility_totals(final, totals, cfg, dyn)
    if cfg.probes.enabled:
        if cfg.pricing.enabled:
            wsteps = pricing_mod.billing_window_steps(cfg.pricing, cfg.dt_h)
            wp = telemetry_mod.window_peak_series(flows["grid_import_kw"],
                                                  wsteps)
        else:
            wp = jnp.zeros_like(flows["grid_import_kw"])
        series = {f: flows[f] for f in EnergyFlow._fields}
        series["soc_kwh"] = flows["soc"]
        series["window_peak_kw"] = wp
        series["queue_depth"] = demand_ys["queue_depth"]
        series["throttle_factor"] = demand_ys["throttle_factor"]
        # the facility chain echoes the derate series it actually applied
        # (ones when healthy); the PDU cap is demand-side, from the inputs
        series["chiller_derate"] = flows["chiller_derate"]
        series["pdu_cap_kw"] = inputs.pdu_cap_kw
        final = final._replace(probes=telemetry_mod.probes_from_series(
            cfg.n_steps, cfg.probes, series))
    if not cfg.collect_series:
        return final, None
    flow = EnergyFlow(**{f: flows[f] for f in EnergyFlow._fields})
    return final, _series(cfg, flow, inputs.ci, demand_ys["n_running"],
                          flows["soc"], demand_ys["max_overcommit"],
                          inputs.wet_bulb_c, inputs.price)


def prepare_run(tasks: TaskTable, hosts: HostTable, ci_trace, cfg: SimConfig,
                dyn: dict | None = None, weather_trace=None):
    """`simulate`'s front half, shared by every executor (the fleet spill
    executor vmaps it over regions).  Checks `dyn`, applies its
    table-shaping keys and the priority presort, builds the scan's
    `StepInputs` and the initial state.  Returns (state0, inputs, dyn,
    inv): `dyn` keeps only the keys the step's ctx reads, and `inv`
    un-permutes the final task table (None without a presort)."""
    dyn = dict(dyn) if dyn else {}
    if not cfg.resilience.enabled:
        bad = [k for k in ("throttle_inlet_c", "pdu_cap_kw",
                           "failure_hazard_scale") if k in dyn]
        if bad:
            raise ValueError(
                f"dyn key(s) {bad} belong to the resilience loop but "
                "cfg.resilience.enabled is False: they would be silently "
                "ignored — enable the subsystem (core/resilience.py)")
    if weather_trace is not None:
        dyn["wet_bulb_trace"] = weather_trace
    if "n_active_hosts" in dyn:
        hosts = scaling_mod.with_scale(hosts, dyn["n_active_hosts"])
    # workload-shaping dyn keys apply to the task table itself, BEFORE the
    # initial state, so both step executors (and any grid vmap over them)
    # see the same typed/re-timed population
    arrival = dyn.pop("arrival_trace", None)
    if arrival is not None:
        tasks = retime_task_table(tasks, arrival)
    interactive_frac = dyn.pop("interactive_frac", None)
    if interactive_frac is not None:
        tasks = with_interactive_frac(
            tasks, interactive_frac, cfg.interactive_grace_h, seed=cfg.seed)
    # priority scheduling: permute rows into (priority desc, arrival) order
    # ONCE, outside the scan, so the per-step priority select runs as the
    # plain FIFO prefix (scheduler.schedule_first_fit presorted path) with
    # no [L*T] level-major flatten+cumsum in the demand hot loop.  `inv`
    # un-permutes the final table, so callers see original row order.
    inv = None
    if _presort_enabled(cfg):
        order = priority_schedule_order(tasks, cfg.scheduler.priority_levels)
        tasks = permute_task_table(tasks, order)
        inv = inverse_permutation(order)
    inputs = build_step_inputs(ci_trace, cfg, dyn=dyn)
    dyn.pop("wet_bulb_trace", None)  # consumed by the inputs, not a ctx key
    dyn.pop("price_trace", None)
    dyn.pop("pv_cf_trace", None)
    dyn.pop("pdu_cap_kw", None)  # folded into inputs.pdu_cap_kw
    state0 = init_sim_state(tasks, hosts, dyn.get("seed", cfg.seed))
    if cfg.probes.enabled:
        state0 = state0._replace(
            probes=telemetry_mod.init_probes(cfg.n_steps, cfg.probes))
    if cfg.resilience.enabled:  # healthy start: no throttle on step 0
        state0 = state0._replace(throttle=jnp.float32(1.0))
    return state0, inputs, dyn, inv


def simulate(tasks: TaskTable, hosts: HostTable, ci_trace, cfg: SimConfig,
             stages: Sequence[Stage] | None = None, dyn: dict | None = None,
             weather_trace=None):
    """Run one simulation.  Returns (final SimState, per-step series or None).

    jit-able; vmap over scenario axes is done by core/grid.py, and
    core/fleet.py vmaps this SAME function over the region axis of a
    multi-datacenter fleet — per-region heterogeneity (host counts, battery
    sizing, setpoints, weather) arrives entirely through `dyn` and
    `weather_trace`, which is what keeps spatial shifting an engine-free
    technique.  `dyn` holds
    traced scenario parameters that static config cannot sweep without
    recompiling: `batt_capacity_kwh` / `batt_rate_kw` (battery sizing),
    `shift_quantile_value` (shifting threshold level), `n_active_hosts`
    (horizontal-scaling mask), `cooling_setpoint` (thermal setpoint),
    `wet_bulb_trace` (f32[S] weather series, also settable via the
    `weather_trace` argument), `price_trace` (f32[S] electricity prices,
    core/pricing.py), `dispatch_lambda` (blended battery-dispatch weight),
    `pv_cf_trace` (f32[S] solar capacity factors, renewabletraces/) and
    `pv_capacity_kw` (PV nameplate sizing, core/renewables.py),
    `slots_per_step` (traced scheduler placement-slot count, masked against
    the static `cfg.scheduler.slots_per_step` bound), `seed`
    (failure-model PRNG), `arrival_trace` (f32[T] per-task arrival hours —
    re-times the task table, state.retime_task_table / grid.tasktrace_axis)
    and `interactive_frac` (traced share of tasks re-typed as interactive
    inference, state.with_interactive_frac).  With cfg.resilience.enabled
    three more: `failure_hazard_scale` (scales host AND facility failure
    hazards; 0.0 = provably healthy), `throttle_inlet_c` (thermal trip
    point) and `pdu_cap_kw` (rack-power clamp while PDU-derated) — see
    core/resilience.py.

    `cfg.backend` picks the executor (module docstring, "Kernel
    backends"); custom `stages` require the stage-pipeline backend.
    """
    if cfg.backend not in BACKENDS:
        raise ValueError(
            f"unknown backend '{cfg.backend}'; pick one of {BACKENDS}")
    if stages is not None and cfg.backend != "stage-pipeline":
        raise ValueError(
            "custom stages compose only with backend='stage-pipeline'; the "
            "megakernel fuses the default facility chain and cannot honour "
            "a replacement pipeline")
    state0, inputs, dyn, inv = prepare_run(tasks, hosts, ci_trace, cfg, dyn,
                                           weather_trace)

    def run():
        if cfg.backend == "megakernel":
            final, ys = _simulate_megakernel(state0, inputs, cfg, dyn)
        else:
            step = build_step_fn(cfg, stages, dyn)
            final, ys = jax.lax.scan(step, state0, inputs)
        if inv is not None:
            final = final._replace(tasks=permute_task_table(final.tasks, inv))
        return final, ys

    # cut a RunRecord only for eager top-level calls: under jit/vmap (grid
    # sweeps, fleet cells) the outer driver records instead, and blocking
    # on tracers is impossible anyway
    if telemetry_mod.enabled() and not telemetry_mod.is_tracing(state0):
        with telemetry_mod.run_recorder("simulate", cfg):
            out = run()
            jax.block_until_ready(out)
        return out
    return run()
