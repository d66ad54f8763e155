"""Final metric extraction (paper's reported quantities).

From the final SimState we derive the paper's headline metrics: total carbon
(operational + embodied), SLA violation fraction, mean task delay, peak power,
energy.  SLA definition (§VI-A): a task meets the SLA if it completes within
`sla_grace_h` (24 h) of its expected completion time (arrival + duration);
tasks still unfinished at the end of the simulation count as violations.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import pricing as pricing_mod
from .config import SimConfig
from .state import DONE, INVALID, N_JOB_CLASSES, SimState


class SimResult(NamedTuple):
    total_carbon_kg: jax.Array
    op_carbon_kg: jax.Array
    emb_carbon_kg: jax.Array
    grid_energy_kwh: jax.Array
    dc_energy_kwh: jax.Array       # facility energy (IT + cooling)
    it_energy_kwh: jax.Array       # IT-equipment energy
    cooling_energy_kwh: jax.Array  # 0 unless cfg.cooling.enabled
    water_l: jax.Array             # cooling-tower evaporation (on-site)
    pue: jax.Array                 # dc_energy / it_energy (1.0 w/o cooling)
    wue_l_per_kwh: jax.Array       # water_l / it_energy (0.0 w/o cooling)
    energy_cost: jax.Array         # currency; 0 unless cfg.pricing.enabled
    demand_cost: jax.Array         # billing-window peak charges (incl. final)
    export_revenue: jax.Array      # export-tariff earnings (renewables)
    total_cost: jax.Array          # energy_cost + demand_cost - export_revenue
    pv_energy_kwh: jax.Array       # on-site generation; 0 unless renewables
    grid_export_kwh: jax.Array     # surplus sold to the grid
    curtailed_kwh: jax.Array       # surplus thrown away (export disallowed)
    heat_reuse_kwh: jax.Array      # reclaimed chiller-path heat (district heat)
    peak_power_kw: jax.Array
    sla_violation_frac: jax.Array
    mean_delay_h: jax.Array        # mean(finish - arrival - duration) over done
    mean_start_delay_h: jax.Array  # mean(first_start - arrival) over started
    done_frac: jax.Array
    n_tasks: jax.Array
    n_interrupts: jax.Array
    n_stops: jax.Array             # graceful shifting pauses (not failures)
    batt_discharged_kwh: jax.Array
    lost_work_h: jax.Array
    # resilience loop (core/resilience.py; all 0 unless resilience.enabled)
    throttled_h: jax.Array         # hours spent thermally throttled
    derate_h: jax.Array            # hours with chiller/PDU equipment derated
    n_spills: jax.Array            # tasks spilled to another region (fleet)
    # raw outcome counts (unclamped): the exact weights fleet aggregation
    # needs to recombine the ratio metrics above across regions
    n_done: jax.Array              # tasks finished within the horizon
    n_started: jax.Array           # tasks that ever started
    n_decided: jax.Array           # SLA denominator (done or past deadline)
    # scheduler work over the run: first-fit placement-loop iterations, and
    # those that placed a task (0 under the aggregate mode); the steps in
    # which more tasks were eligible than the placement bound took
    first_fit_iters: jax.Array
    first_fit_placed: jax.Array
    slot_bound_steps: jax.Array
    # per-class SLA/latency metrics, indexed by the state.JOB_* codes
    # (batch, training, interactive) — the performance leg of sweeps that
    # trade carbon against latency (examples/slo_tradeoff.py).  The class
    # axis is TRAILING so fleet stacking/vmap leading axes compose; the raw
    # per-class counts recombine across regions exactly like the totals
    class_sla_violation_frac: jax.Array  # f32[C] violations / decided
    class_mean_start_delay_h: jax.Array  # f32[C] mean first_start - arrival
    class_n_violations: jax.Array        # f32[C]; sums to the total count
    class_n_decided: jax.Array           # f32[C]; sums to n_decided
    class_n_started: jax.Array           # f32[C]; sums to n_started
    # opt-in probe-bus samples (telemetry.Probes, cfg.probes.enabled);
    # None by default — a leafless trailing pytree node, so results,
    # goldens and fleet aggregation are untouched unless probing is on
    probes: Any = None


def summarize(state: SimState, cfg: SimConfig) -> SimResult:
    tasks, m = state.tasks, state.metrics
    t_end = state.t
    # tasks that never arrive within the simulated horizon are out of scope
    arrived = (tasks.status != INVALID) & (tasks.arrival <= t_end)
    done = tasks.status == DONE

    expected = tasks.arrival + tasks.duration
    # per-task SLA grace where set (>= 0, e.g. interactive latency SLOs);
    # the -1 sentinel falls back to the config-wide grace, so untyped
    # tables reproduce the flat-deadline pipeline bit-for-bit
    grace = jnp.where(tasks.sla_grace >= 0.0, tasks.sla_grace,
                      jnp.float32(cfg.sla_grace_h))
    deadline = expected + grace
    violated_done = done & (tasks.finish > deadline)
    # undone tasks only count once their SLA deadline has actually passed
    violated_undone = arrived & ~done & (deadline <= t_end)
    # SLA denominator: tasks whose outcome is decided within the horizon
    decided = done | violated_undone
    n_decided = jnp.maximum(jnp.sum(decided.astype(jnp.float32)), 1.0)
    n_viol = jnp.sum(violated_done.astype(jnp.float32)) + jnp.sum(
        violated_undone.astype(jnp.float32))
    n_arrived = jnp.sum(arrived.astype(jnp.float32))
    n_valid = jnp.maximum(n_arrived, 1.0)

    n_done = jnp.maximum(jnp.sum(done.astype(jnp.float32)), 1.0)
    delay = jnp.where(done, jnp.maximum(tasks.finish - expected, 0.0), 0.0)
    started = arrived & jnp.isfinite(tasks.first_start)
    n_started = jnp.maximum(jnp.sum(started.astype(jnp.float32)), 1.0)
    sdelay = jnp.where(started, tasks.first_start - tasks.arrival, 0.0)

    # per-class splits via ONE masked [M, C, T] reduction over the stacked
    # per-task vectors (scatter-free, and — unlike a dot — the vmapped
    # lowering reduces each (metric, class) row in the same order as the
    # unbatched one, keeping simulate_fleet R=1 bitwise == simulate); the
    # four separate [C, T] reductions this fuses cost four broadcasts of
    # the class mask per grid cell.  violated_done and violated_undone are
    # disjoint (done vs not-done), so the class counts sum exactly to the
    # totals above
    cw = (tasks.job_class[None, :]
          == jnp.arange(N_JOB_CLASSES, dtype=jnp.int32)[:, None])
    stacked = jnp.stack([
        (violated_done | violated_undone).astype(jnp.float32),
        decided.astype(jnp.float32),
        started.astype(jnp.float32),
        sdelay])                                             # [M, T]
    class_n_viol, class_n_decided, class_n_started, class_sdelay = jnp.sum(
        jnp.where(cw[None, :, :], stacked[:, None, :], 0.0), axis=-1)

    it_safe = jnp.maximum(m.it_energy, 1e-9)
    # settle the final (still open) demand-charge billing window
    demand_cost = pricing_mod.settle_demand_charge(
        m.demand_cost, m.window_peak_kw, cfg.pricing)
    return SimResult(
        total_carbon_kg=m.op_carbon + m.emb_carbon,
        op_carbon_kg=m.op_carbon,
        emb_carbon_kg=m.emb_carbon,
        grid_energy_kwh=m.grid_energy,
        dc_energy_kwh=m.dc_energy,
        it_energy_kwh=m.it_energy,
        cooling_energy_kwh=m.cooling_energy,
        water_l=m.water_l,
        pue=m.dc_energy / it_safe,
        wue_l_per_kwh=m.water_l / it_safe,
        energy_cost=m.energy_cost,
        demand_cost=demand_cost,
        export_revenue=m.export_revenue,
        total_cost=m.energy_cost + demand_cost - m.export_revenue,
        pv_energy_kwh=m.pv_energy,
        grid_export_kwh=m.export_energy,
        curtailed_kwh=m.curtailed_energy,
        heat_reuse_kwh=m.heat_reuse,
        peak_power_kw=m.peak_power,
        sla_violation_frac=n_viol / n_decided,
        mean_delay_h=jnp.sum(delay) / n_done,
        mean_start_delay_h=jnp.sum(sdelay) / n_started,
        done_frac=jnp.sum(done.astype(jnp.float32)) / n_valid,
        # raw arrived count (no min-1 clamp): fleet_totals sums and weights
        # by it, and a clamp would phantom-count empty regions
        n_tasks=n_arrived,
        n_interrupts=m.n_interrupts,
        n_stops=m.n_stops,
        batt_discharged_kwh=m.batt_discharged,
        lost_work_h=jnp.sum(jnp.where(arrived, tasks.lost_work, 0.0)),
        throttled_h=m.throttled_h,
        derate_h=m.derate_h,
        n_spills=m.n_spills,
        n_done=jnp.sum(done.astype(jnp.float32)),
        n_started=jnp.sum(started.astype(jnp.float32)),
        n_decided=jnp.sum(decided.astype(jnp.float32)),
        first_fit_iters=m.first_fit_iters,
        first_fit_placed=m.first_fit_placed,
        slot_bound_steps=m.slot_bound_steps,
        class_sla_violation_frac=class_n_viol
        / jnp.maximum(class_n_decided, 1.0),
        class_mean_start_delay_h=class_sdelay
        / jnp.maximum(class_n_started, 1.0),
        class_n_violations=class_n_viol,
        class_n_decided=class_n_decided,
        class_n_started=class_n_started,
        probes=state.probes,
    )


def fleet_totals(per_region: SimResult, axis: int = 0) -> SimResult:
    """Aggregate per-region SimResults into one fleet-level SimResult.

    Additive fields (carbon, energy, water, counts, lost work) sum over the
    region axis; ratio fields recombine EXACTLY from the raw outcome counts
    (`n_done`/`n_started`/`n_decided`) rather than averaging the per-region
    ratios, so a region with 3 tasks cannot outvote one with 3000.  PUE and
    WUE are recomputed from the summed energies (fleet PUE is the
    energy-weighted one).  `peak_power_kw` is the sum of per-region peaks:
    regions are separate facilities, each provisioning its own grid feed, so
    the fleet-level figure is the provisioning total (an upper bound on the
    coincident peak).  Costs sum for the same reason — each facility is
    billed on its own meter, demand charges included.  jit/vmap-safe: pure
    jnp on stacked fields.
    """
    def s(x):
        return jnp.sum(x, axis=axis)

    def wmean(value, weight):
        return (jnp.sum(value * weight, axis=axis)
                / jnp.maximum(s(weight), 1.0))

    p = per_region
    it_safe = jnp.maximum(s(p.it_energy_kwh), 1e-9)
    return SimResult(
        total_carbon_kg=s(p.total_carbon_kg),
        op_carbon_kg=s(p.op_carbon_kg),
        emb_carbon_kg=s(p.emb_carbon_kg),
        grid_energy_kwh=s(p.grid_energy_kwh),
        dc_energy_kwh=s(p.dc_energy_kwh),
        it_energy_kwh=s(p.it_energy_kwh),
        cooling_energy_kwh=s(p.cooling_energy_kwh),
        water_l=s(p.water_l),
        pue=s(p.dc_energy_kwh) / it_safe,
        wue_l_per_kwh=s(p.water_l) / it_safe,
        energy_cost=s(p.energy_cost),
        demand_cost=s(p.demand_cost),
        export_revenue=s(p.export_revenue),
        total_cost=s(p.total_cost),
        pv_energy_kwh=s(p.pv_energy_kwh),
        grid_export_kwh=s(p.grid_export_kwh),
        curtailed_kwh=s(p.curtailed_kwh),
        heat_reuse_kwh=s(p.heat_reuse_kwh),
        peak_power_kw=s(p.peak_power_kw),
        sla_violation_frac=wmean(p.sla_violation_frac, p.n_decided),
        mean_delay_h=wmean(p.mean_delay_h, p.n_done),
        mean_start_delay_h=wmean(p.mean_start_delay_h, p.n_started),
        done_frac=wmean(p.done_frac, p.n_tasks),
        n_tasks=s(p.n_tasks),
        n_interrupts=s(p.n_interrupts),
        n_stops=s(p.n_stops),
        batt_discharged_kwh=s(p.batt_discharged_kwh),
        lost_work_h=s(p.lost_work_h),
        throttled_h=s(p.throttled_h),
        derate_h=s(p.derate_h),
        n_spills=s(p.n_spills),
        n_done=s(p.n_done),
        n_started=s(p.n_started),
        n_decided=s(p.n_decided),
        first_fit_iters=s(p.first_fit_iters),
        first_fit_placed=s(p.first_fit_placed),
        slot_bound_steps=s(p.slot_bound_steps),
        # class fields are [R, C]: sum/recombine over the region axis,
        # keeping the trailing class axis
        class_sla_violation_frac=(s(p.class_n_violations)
                                  / jnp.maximum(s(p.class_n_decided), 1.0)),
        class_mean_start_delay_h=wmean(p.class_mean_start_delay_h,
                                       p.class_n_started),
        class_n_violations=s(p.class_n_violations),
        class_n_decided=s(p.class_n_decided),
        class_n_started=s(p.class_n_started),
    )


def carbon_reduction_pct(baseline: SimResult, treated: SimResult):
    """Positive = treated emits less total carbon than baseline."""
    return 100.0 * (1.0 - treated.total_carbon_kg
                    / jnp.maximum(baseline.total_carbon_kg, 1e-9))


# ---------------------------------------------------------------------------
# §XI extensions: water consumption and monetary cost
# ---------------------------------------------------------------------------

class SustainabilityExtras(NamedTuple):
    """Paper §XI names water usage and monetary cost as the next metrics.
    Water and cost now have first-class simulated counterparts (the thermal
    subsystem, core/thermal.py, and the pricing subsystem, core/pricing.py);
    this post-processing composes onto any SimResult and falls back to the
    legacy flat-intensity estimates when a subsystem did not run."""
    water_l: jax.Array        # on-site + upstream water, litres
    energy_cost: jax.Array    # electricity bill, currency units
    heat_credit_kg: jax.Array # CO2 displaced by reclaimed district heat


def sustainability_extras(res: SimResult, *, cfg: SimConfig | None = None,
                          wue_l_per_kwh: float = 1.8,
                          water_intensity_l_per_kwh: float = 1.6,
                          price_per_kwh: float = 0.12,
                          displaced_heat_kg_per_kwh: float = 0.2,
                          simulated_water: bool | None = None,
                          simulated_cost: bool | None = None,
                          ) -> SustainabilityExtras:
    """On-site water: the *simulated* cooling-tower evaporation when the
    thermal subsystem ran, else the legacy flat-WUE estimate (~1.8 L/kWh).
    Cost: the *simulated* bill (energy + demand charges, core/pricing.py)
    when the pricing subsystem ran, else the legacy flat tariff
    `price_per_kwh * grid_energy` — the pre-pricing behaviour, kept as the
    documented fallback exactly like the flat-WUE path.

    Pass `cfg` (or `simulated_water`/`simulated_cost` explicitly) when you
    know which subsystems were simulated — callers that hold the SimConfig
    always do, and threading `cfg.cooling.enabled`/`cfg.pricing.enabled`
    through avoids the per-cell inference below.  Without it, water is
    inferred from `cooling_energy_kwh > 0` (which misfires in the
    degenerate zero-fan-overhead fully-economized case: cooling ran, used
    no energy, evaporated no water, and the flat estimate wrongly kicks
    in) and cost from `total_cost != 0 or export_revenue > 0` (a simulated
    bill may be zero or negative once the export tariff runs; the
    inference still misfires on an all-zero-price trace, where the real
    bill of exactly 0 is indistinguishable from pricing never running).
    Upstream water intensity of generation (~1.6 L/kWh grid
    average) is always estimate-based.  Regionalized values can be passed
    per sweep exactly like carbon traces.

    `heat_credit_kg` is the district-heating credit for reclaimed
    chiller-path heat (`cfg.cooling.heat_reuse_fraction`, core/thermal.py):
    every reclaimed kWh displaces `displaced_heat_kg_per_kwh` of heating
    emissions (~0.2 kg/kWh for a gas boiler).  Zero whenever heat reuse is
    off — the credit composes onto any SimResult without touching the
    simulated carbon totals (report it separately or subtract it
    deliberately: avoided emissions are not operational carbon)."""
    if cfg is not None:
        if simulated_water is None:
            simulated_water = cfg.cooling.enabled
        if simulated_cost is None:
            simulated_cost = cfg.pricing.enabled
    if simulated_water is None:
        onsite = jnp.where(res.cooling_energy_kwh > 0.0, res.water_l,
                           res.dc_energy_kwh * wue_l_per_kwh)
    elif simulated_water:
        onsite = res.water_l
    else:
        onsite = res.dc_energy_kwh * wue_l_per_kwh
    water = onsite + res.grid_energy_kwh * water_intensity_l_per_kwh
    flat_cost = pricing_mod.flat_energy_cost(res.grid_energy_kwh,
                                             price_per_kwh)
    if simulated_cost is None:
        # a simulated bill may be zero or NEGATIVE once the export tariff
        # runs (revenue can exceed the import charges), so the inference
        # keys on any nonzero cost OR any export revenue — only the
        # all-zero-price-trace degenerate case still misfires (documented)
        simulated = (res.total_cost != 0.0) | (res.export_revenue > 0.0)
        cost = jnp.where(simulated, res.total_cost, flat_cost)
    elif simulated_cost:
        cost = res.total_cost
    else:
        cost = flat_cost
    heat_credit = res.heat_reuse_kwh * displaced_heat_kg_per_kwh
    return SustainabilityExtras(water_l=water, energy_cost=cost,
                                heat_credit_kg=heat_credit)
