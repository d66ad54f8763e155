"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_CURVES = {
    "linear": lambda u: u,
    "sqrt": lambda u: jnp.sqrt(u),
    "square": lambda u: u * u,
    "cubic": lambda u: u * u * u,
}


def fused_power_carbon(cpu_util, gpu_util, n_gpus, on, ci, dt_h, *,
                       cpu_idle, cpu_max, cpu_curve, gpu_idle, gpu_max,
                       gpu_curve):
    cpu_u = jnp.clip(cpu_util, 0.0, 1.0)
    gpu_u = jnp.clip(gpu_util, 0.0, 1.0)
    p_cpu = cpu_idle + (cpu_max - cpu_idle) * _CURVES[cpu_curve](cpu_u)
    p_gpu = (gpu_idle + (gpu_max - gpu_idle) * _CURVES[gpu_curve](gpu_u)) * n_gpus
    p_kw = (p_cpu + p_gpu) * on / 1000.0
    dc = jnp.sum(p_kw)
    return p_kw, dc, dc * dt_h * ci / 1000.0


def ssd_chunk(x, dt, a, b, c, chunk: int = 64):
    """Mamba-2 SSD reference: exact sequential state-space recurrence.

    x:  f32[T, H, P]   inputs per head
    dt: f32[T, H]      softplus-ed step sizes (>0)
    a:  f32[H]         negative state decay rates (A = -exp(a_log))
    b:  f32[T, G, N]   input projections (G groups broadcast over H)
    c:  f32[T, G, N]   output projections
    Returns y: f32[T, H, P] with y_t = C_t^T h_t,
    h_t = exp(dt_t * a) h_{t-1} + dt_t * B_t x_t^T  (per head, state [N, P]).
    """
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    heads_per_group = h // g
    bh = jnp.repeat(b, heads_per_group, axis=1)     # [T, H, N]
    ch = jnp.repeat(c, heads_per_group, axis=1)

    def step(state, inp):
        xt, dtt, bt, ct = inp                       # [H,P],[H],[H,N],[H,N]
        decay = jnp.exp(dtt * a)[:, None, None]     # [H,1,1]
        upd = (dtt[:, None] * bt)[..., None] * xt[:, None, :]  # [H,N,P]
        state = state * decay + upd
        y = jnp.einsum("hn,hnp->hp", ct, state)
        return state, y

    state0 = jnp.zeros((h, n, p), jnp.float32)
    _, y = jax.lax.scan(step, state0, (x, dt, bh, ch))
    return y


# ---------------------------------------------------------------------------
# fused facility chain: the megakernel oracle (core/engine.py backend=
# 'megakernel', kernels/fused_step.py correctness reference)
# ---------------------------------------------------------------------------

def fused_facility_chain(it_kw, ci, wet_bulb_c, price, price_lo, price_hi,
                         pv_cf, batt_threshold, ci_rising, dt_h, cfg, *,
                         soc0=0.0, setpoint_c=None, batt_capacity_kwh=None,
                         batt_rate_kw=None, dispatch_lambda=None,
                         pv_capacity_kw=None, chiller_derate=None):
    """The whole facility pipeline (cooling -> renewables -> battery ->
    net metering) vectorized over the time axis.  Returns a dict of f32[S]
    per-step flow series plus the battery SoC trajectory.

    This is the pure-jnp statement of the fused step: everything except the
    battery state-of-charge recurrence is elementwise in t, so it runs as
    [S]-wide vector math instead of S sequential scan steps.  The SoC
    recurrence keeps a minimal `lax.scan` whose carry is ONE scalar (the
    stage-pipeline scan drags the full task/host tables through every
    step).  Dispatch decisions factor out of the recurrence exactly: the
    only SoC-dependence in `battery.dispatch_decision` is the final
    `& (charge > 0)` discharge guard, which is reapplied inside the scan —
    so per step the flows compute the SAME arithmetic as `core/engine.py`'s
    stage pipeline (agreeing to ULP-level rounding; XLA schedules the
    vectorized form differently than the scalar scan body).

    Flow keys mirror `engine.EnergyFlow`; extras: `water_l_per_h`,
    `heat_reuse_kw`, `soc` (post-step charge, kWh), `want_charge` (the
    final dispatch decision, for `BatteryState.was_charging`) and
    `chiller_derate` (the derate series the cooling model applied — ones
    when healthy — consumed by the probe-bus export).

    `chiller_derate` (f32[S] facility-failure series, core/resilience.py)
    degrades the cooling model exactly as `stage_cooling` does — it is
    elementwise in t, so the facility half stays vectorized even with the
    failure loop closed.  None is the bitwise healthy path.
    """
    from repro.core import battery as battery_mod
    from repro.core import renewables as renewables_mod
    from repro.core import thermal as thermal_mod

    it_kw = jnp.asarray(it_kw, jnp.float32)
    zeros = jnp.zeros_like(it_kw)
    dt = jnp.float32(dt_h)

    # cooling: elementwise in t (core/thermal.py is pure jnp)
    if cfg.cooling.enabled:
        cooling_kw, water_l_per_h = thermal_mod.cooling_step(
            it_kw, wet_bulb_c, cfg.cooling, setpoint_c=setpoint_c,
            chiller_derate=chiller_derate)
        reuse = cfg.cooling.heat_reuse_fraction
        if reuse > 0.0:
            heat_reuse_kw = reuse * thermal_mod.reclaimable_heat_kw(
                it_kw, cooling_kw, wet_bulb_c, cfg.cooling,
                setpoint_c=setpoint_c, chiller_derate=chiller_derate)
            water_l_per_h = water_l_per_h * (1.0 - reuse)
        else:
            heat_reuse_kw = zeros
    else:
        cooling_kw = water_l_per_h = heat_reuse_kw = zeros
    load = it_kw + cooling_kw

    # renewables: PV supply netted against the facility load
    if cfg.renewables.enabled:
        cap_kw = (jnp.float32(cfg.renewables.pv_capacity_kw)
                  if pv_capacity_kw is None else pv_capacity_kw)
        pv_kw = renewables_mod.pv_power_kw(cap_kw, pv_cf)
        net_load, surplus = renewables_mod.net_load_split(load, pv_kw)
    else:
        pv_kw, net_load, surplus = zeros, load, None

    if cfg.battery.enabled:
        bcfg = cfg.battery
        cap = (jnp.float32(bcfg.capacity_kwh) if batt_capacity_kwh is None
               else batt_capacity_kwh)
        rate = (cap * bcfg.charge_rate_kw_per_kwh if batt_rate_kw is None
                else batt_rate_kw)
        eff = jnp.float32(bcfg.round_trip_efficiency)
        # policy decisions for ALL steps at once; charge=1 makes the
        # (charge > 0) discharge factor vacuous here — it is reapplied as
        # (soc > 0) inside the recurrence, which is exact (see docstring)
        wc, wd = battery_mod.dispatch_decision(
            bcfg, jnp.ones_like(it_kw), ci, batt_threshold, ci_rising,
            price=price, price_lo=price_lo, price_hi=price_hi,
            dispatch_lambda=dispatch_lambda)
        if surplus is not None:
            wc, wd, charge_cap_kw = battery_mod.surplus_aware_dispatch(
                wc, wd, surplus)
        else:
            charge_cap_kw = jnp.full_like(it_kw, jnp.inf)

        def body(soc, x):
            wc_t, wd_t, ccap_t, net_t = x
            headroom_kw = (cap - soc) / dt
            ck = jnp.minimum(rate, jnp.maximum(headroom_kw, 0.0))
            ck = jnp.minimum(ck, ccap_t)
            ck = jnp.where(wc_t, ck, 0.0)
            avail_kw = soc / dt
            dk = jnp.minimum(jnp.minimum(rate, avail_kw), net_t)
            dk = jnp.where(wd_t & (soc > 0.0) & ~wc_t, dk, 0.0)
            soc = jnp.clip(soc + (ck * eff - dk) * dt, 0.0, cap)
            return soc, (soc, ck, dk)

        _, (soc, charge_kw, discharge_kw) = jax.lax.scan(
            body, jnp.float32(soc0), (wc, wd, charge_cap_kw, net_load))
        want_charge = wc
    else:
        soc = charge_kw = discharge_kw = zeros
        want_charge = jnp.zeros_like(it_kw, dtype=bool)

    # settle the grid side of the ledger (mirrors stage_battery /
    # stage_net_meter in core/engine.py)
    if cfg.renewables.enabled:
        if cfg.battery.enabled:
            pv_to_batt, export_kw, curtailed_kw = renewables_mod.split_surplus(
                surplus, charge_kw, cfg.renewables)
            grid_import_kw = net_load + (charge_kw - pv_to_batt) - discharge_kw
        else:
            _, export_kw, curtailed_kw = renewables_mod.split_surplus(
                surplus, zeros, cfg.renewables)
            grid_import_kw = net_load
    else:
        export_kw = curtailed_kw = zeros
        grid_import_kw = load + charge_kw - discharge_kw

    return {"it_kw": it_kw, "cooling_kw": cooling_kw, "pv_kw": pv_kw,
            "batt_charge_kw": charge_kw, "batt_discharge_kw": discharge_kw,
            "grid_import_kw": grid_import_kw, "grid_export_kw": export_kw,
            "curtailed_kw": curtailed_kw, "water_l_per_h": water_l_per_h,
            "heat_reuse_kw": heat_reuse_kw, "soc": soc,
            "want_charge": want_charge,
            # the derate series the cooling model actually applied (ones =
            # healthy): echoed so the probe bus reads every facility-side
            # channel from one flows dict instead of re-deriving it
            "chiller_derate": (jnp.ones_like(it_kw) if chiller_derate is None
                               else jnp.broadcast_to(
                                   jnp.asarray(chiller_derate, jnp.float32),
                                   it_kw.shape))}
