"""Time-blocked fused facility megakernel (the Pallas form of the chain).

`core/engine.py` backend='megakernel' splits a simulation at its one true
sequential boundary; this kernel is the FACILITY half (cooling ->
renewables -> battery -> pricing -> carbon) executed as ONE `pallas_call`
over a sequential time grid:

  * the horizon S is blocked into `_BLOCK_T`-step tiles; per block, the
    elementwise physics (cooling COP curve, PV netting, dispatch policy
    decisions) runs as [1, B] vector math straight from the engine's own
    core modules — the kernel body is jnp, so thermal/renewables/battery
    formulas are single-sourced, never transcribed;
  * the two scalar recurrences (battery SoC, billing-window peak) walk the
    block in a `fori_loop` whose carries are (1, 1) vectors, reading step j
    of each block row by a lane mask, and pass from tile to tile in the
    accumulator row — nothing per-step ever returns to HBM; the traced
    parameters and dequantization constants sit in SMEM;
  * the four exogenous traces (carbon intensity, wet-bulb, price, PV
    capacity factor) arrive QUANTIZED (core/quant.py: bf16 or int8 affine)
    and are dequantized on read inside the kernel, so HBM traffic for the
    dominant [S] inputs is halved/quartered;
  * the only output is one f32[1, 128] accumulator row of run totals
    (energy/carbon/cost/water sums, grid peak, final SoC) — the quantities
    `engine._merge_facility_totals` folds into the metrics.

Matches `kernels/ref.fused_facility_chain` + `engine.facility_totals_from_
flows` within float tolerance (tests/test_megakernel.py); exact given
`trace_store='f32'` inputs up to sum reassociation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import telemetry
from repro.core.quant import QuantizedTrace, quantize_trace

_LANE = 128
_BLOCK_T = 256          # time steps per tile (2 lanes-rows of the VPU)

# dense f32[8, S] row indices (f32 tile-aligned: 8 sublanes exactly);
# _R_CLOSE flags the steps that close a billing window
_R_IT, _R_BT, _R_RISING, _R_PLO, _R_PHI, _R_CLOSE = range(6)
# traced-parameter slots of the f32[1, 8] params block (SMEM)
_P_CAP, _P_RATE, _P_PVCAP, _P_SETPOINT, _P_SOC0, _P_LAMBDA = range(6)
# accumulator-row lanes (the kernel's only output, f32[1, 128])
(_A_SOC, _A_WPEAK, _A_WASC, _A_DEMAND, _A_GRID, _A_GRID_CI, _A_GRID_PR,
 _A_GRID_MAX, _A_IT, _A_COOL, _A_WATER, _A_HEAT, _A_PV, _A_CK, _A_DK,
 _A_EXP, _A_EXP_PR, _A_CUR) = range(18)


def _dequant_row(q_ref, meta_ref, k: int):
    """f32[1, B] reconstruction of quantized-trace row k (dequant-on-read);
    the scale/zero pair is read from the SMEM meta block."""
    return (q_ref[...].astype(jnp.float32) * meta_ref[0, 2 * k]
            + meta_ref[0, 2 * k + 1])


def _kernel(dense_ref, qci_ref, qwb_ref, qpr_ref, qpv_ref, meta_ref,
            par_ref, acc_ref, *, cfg, n_steps: int):
    from repro.core import battery as battery_mod
    from repro.core import renewables as renewables_mod
    from repro.core import thermal as thermal_mod

    i = pl.program_id(0)
    b = _BLOCK_T
    dt = jnp.float32(cfg.dt_h)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    valid = (i * b + lane) < n_steps
    vf = valid.astype(jnp.float32)

    it_kw = dense_ref[_R_IT:_R_IT + 1, :]
    ci = _dequant_row(qci_ref, meta_ref, 0)
    wb = _dequant_row(qwb_ref, meta_ref, 1)
    price = _dequant_row(qpr_ref, meta_ref, 2)
    pv_cf = _dequant_row(qpv_ref, meta_ref, 3)

    # --- elementwise physics, straight from the core modules -------------
    if cfg.cooling.enabled:
        sp = par_ref[0, _P_SETPOINT]
        cooling_kw, water = thermal_mod.cooling_step(it_kw, wb, cfg.cooling,
                                                     setpoint_c=sp)
        reuse = cfg.cooling.heat_reuse_fraction
        if reuse > 0.0:
            heat = reuse * thermal_mod.reclaimable_heat_kw(
                it_kw, cooling_kw, wb, cfg.cooling, setpoint_c=sp)
            water = water * (1.0 - reuse)
        else:
            heat = jnp.zeros_like(it_kw)
    else:
        cooling_kw = water = heat = jnp.zeros_like(it_kw)
    load = it_kw + cooling_kw

    if cfg.renewables.enabled:
        pv_kw = renewables_mod.pv_power_kw(par_ref[0, _P_PVCAP], pv_cf)
        net_load, surplus = renewables_mod.net_load_split(load, pv_kw)
    else:
        pv_kw = jnp.zeros_like(it_kw)
        net_load = load

    if cfg.battery.enabled:
        wc, wd = battery_mod.dispatch_decision(
            cfg.battery, jnp.ones_like(it_kw), ci,
            dense_ref[_R_BT:_R_BT + 1, :],
            dense_ref[_R_RISING:_R_RISING + 1, :] > 0.5,
            price=price, price_lo=dense_ref[_R_PLO:_R_PLO + 1, :],
            price_hi=dense_ref[_R_PHI:_R_PHI + 1, :],
            dispatch_lambda=par_ref[0, _P_LAMBDA])
        if cfg.renewables.enabled:
            wc, wd, ccap = battery_mod.surplus_aware_dispatch(wc, wd, surplus)
        wc = wc.astype(jnp.float32)
        wd = wd.astype(jnp.float32)

    # The accumulator row is read and written whole: the chip has no
    # scalar access to VMEM, so one lane is picked or set by a lane mask.
    acc_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.where(acc_lane == _A_SOC, par_ref[0, _P_SOC0],
                                 0.0)

    def lane_sum(row):
        return jnp.sum(row, axis=1, keepdims=True)

    acc = acc_ref[...]
    # block-local sums of the purely elementwise series
    for k, row in ((_A_IT, it_kw), (_A_COOL, cooling_kw), (_A_WATER, water),
                   (_A_HEAT, heat), (_A_PV, pv_kw)):
        acc = acc + jnp.where(acc_lane == k, lane_sum(row * vf), 0.0)

    # --- the sequential tail: SoC + billing-window recurrences -----------
    # Each carry is a (1, 1) vector, and step j reads lane j of a block row
    # by a masked lane sum (exact: every other lane adds zero).
    cap = par_ref[0, _P_CAP]
    rate = par_ref[0, _P_RATE]
    eff = jnp.float32(cfg.battery.round_trip_efficiency)
    dchg = jnp.float32(cfg.pricing.demand_charge_per_kw)
    close_row = dense_ref[_R_CLOSE:_R_CLOSE + 1, :]
    zero = jnp.zeros((1, 1), jnp.float32)

    def step(j, carry):
        (soc, wpeak, wasc, demand, s_g, s_gci, s_gpr, m_g, s_ck, s_dk,
         s_exp, s_expp, s_cur) = carry
        at = lambda row: lane_sum(jnp.where(lane == j, row, 0.0))
        mask = at(vf)
        v = mask > 0.5
        net_t = at(net_load)
        ci_t = at(ci)
        pr_t = at(price)
        if cfg.battery.enabled:
            wc_t = (at(wc) > 0.5) & v
            ck = jnp.minimum(rate, jnp.maximum((cap - soc) / dt, 0.0))
            if cfg.renewables.enabled:
                ck = jnp.minimum(ck, at(ccap))
            ck = jnp.where(wc_t, ck, 0.0)
            dk = jnp.minimum(jnp.minimum(rate, soc / dt), net_t)
            dk = jnp.where((at(wd) > 0.5) & (soc > 0.0) & ~wc_t & v, dk, 0.0)
            soc = jnp.clip(soc + (ck * eff - dk) * dt, 0.0, cap)
            wasc = jnp.where(v, wc_t.astype(jnp.float32), wasc)
        else:
            ck = dk = zero
        if cfg.renewables.enabled:
            sur_t = at(surplus)
            pv_to_batt = jnp.minimum(ck, sur_t)
            rem = sur_t - pv_to_batt
            exp_t = rem if cfg.renewables.export_allowed else zero
            cur_t = zero if cfg.renewables.export_allowed else rem
            grid = net_t + (ck - pv_to_batt) - dk
        else:
            exp_t = cur_t = zero
            grid = net_t + ck - dk
        grid = jnp.where(v, grid, 0.0)     # flows are >= 0: masking is exact
        if cfg.pricing.enabled:
            close = at(close_row) > 0.5
            demand = demand + jnp.where(close, wpeak * dchg, 0.0)
            wpeak = jnp.where(v, jnp.maximum(jnp.where(close, 0.0, wpeak),
                                             grid), wpeak)
        return (soc, wpeak, wasc, demand, s_g + grid, s_gci + grid * ci_t,
                s_gpr + grid * pr_t * mask, jnp.maximum(m_g, grid),
                s_ck + ck, s_dk + dk, s_exp + exp_t * mask,
                s_expp + exp_t * pr_t * mask, s_cur + cur_t * mask)

    carry_lanes = (_A_SOC, _A_WPEAK, _A_WASC, _A_DEMAND, _A_GRID, _A_GRID_CI,
                   _A_GRID_PR, _A_GRID_MAX, _A_CK, _A_DK, _A_EXP, _A_EXP_PR,
                   _A_CUR)
    carry0 = tuple(lane_sum(jnp.where(acc_lane == k, acc, 0.0))
                   for k in carry_lanes)
    out = jax.lax.fori_loop(0, b, step, carry0)
    for k, val in zip(carry_lanes, out):
        acc = jnp.where(acc_lane == k, val, acc)
    acc_ref[...] = acc


def _quantize(x, store: str) -> QuantizedTrace:
    if store == "f32":
        x = jnp.asarray(x, jnp.float32)
        ones = jnp.ones(x.shape[:-1] + (1,), jnp.float32)
        return QuantizedTrace(q=x, scale=ones, zero=jnp.zeros_like(ones))
    return quantize_trace(x, store)


def _pad_t(x, sp: int):
    x = jnp.asarray(x)
    return jnp.pad(x, (0, sp - x.shape[0])).reshape(1, sp)


@functools.partial(jax.jit, static_argnames=("cfg", "trace_store",
                                             "interpret"))
def fused_facility_totals(it_kw, ci, wet_bulb_c, price, price_lo, price_hi,
                          pv_cf, batt_threshold, ci_rising, cfg, *,
                          trace_store: str = "bf16", soc0=0.0,
                          setpoint_c=None, batt_capacity_kwh=None,
                          batt_rate_kw=None, dispatch_lambda=None,
                          pv_capacity_kw=None, interpret: bool = True):
    """Run the facility chain over all S steps in one pallas_call; returns
    the totals dict of `engine.facility_totals_from_flows` (same keys,
    pricing/export entries gated identically).

    All series are f32[S]; the dyn scalars may be traced (grid axes).
    `trace_store` picks the HBM representation of the four exogenous
    traces ('f32' | 'bf16' | 'int8', core/quant.py).
    """
    s = it_kw.shape[0]
    n_blocks = max(-(-s // _BLOCK_T), 1)
    sp = n_blocks * _BLOCK_T
    dt = jnp.float32(cfg.dt_h)

    qts = [_quantize(jnp.asarray(x, jnp.float32), trace_store)
           for x in (ci, wet_bulb_c, price, pv_cf)]
    meta = jnp.stack([v for qt in qts
                      for v in (qt.scale[0], qt.zero[0])]).reshape(1, 8)
    qrows = [_pad_t(qt.q, sp) for qt in qts]

    dense = jnp.zeros((8, sp), jnp.float32)
    dense = dense.at[_R_IT, :s].set(jnp.asarray(it_kw, jnp.float32))
    dense = dense.at[_R_BT, :s].set(jnp.asarray(batt_threshold, jnp.float32))
    dense = dense.at[_R_RISING, :s].set(
        jnp.asarray(ci_rising).astype(jnp.float32))
    dense = dense.at[_R_PLO, :s].set(jnp.asarray(price_lo, jnp.float32))
    dense = dense.at[_R_PHI, :s].set(jnp.asarray(price_hi, jnp.float32))

    bcfg = cfg.battery
    cap = (jnp.float32(bcfg.capacity_kwh) if batt_capacity_kwh is None
           else batt_capacity_kwh)
    params = jnp.zeros((1, 8), jnp.float32)
    params = params.at[0, _P_CAP].set(cap)
    params = params.at[0, _P_RATE].set(
        cap * bcfg.charge_rate_kw_per_kwh if batt_rate_kw is None
        else batt_rate_kw)
    params = params.at[0, _P_PVCAP].set(
        jnp.float32(cfg.renewables.pv_capacity_kw) if pv_capacity_kw is None
        else pv_capacity_kw)
    params = params.at[0, _P_SETPOINT].set(
        jnp.float32(cfg.cooling.setpoint_c) if setpoint_c is None
        else setpoint_c)
    params = params.at[0, _P_SOC0].set(soc0)
    params = params.at[0, _P_LAMBDA].set(
        jnp.float32(bcfg.dispatch_lambda) if dispatch_lambda is None
        else dispatch_lambda)

    if cfg.pricing.enabled:
        from repro.core import pricing as pricing_mod
        wsteps = pricing_mod.billing_window_steps(cfg.pricing, cfg.dt_h)
        t = jnp.arange(s)
        dense = dense.at[_R_CLOSE, :s].set(
            ((t % wsteps == 0) & (t > 0)).astype(jnp.float32))

    kern = functools.partial(_kernel, cfg=cfg, n_steps=s)
    trow = lambda: pl.BlockSpec((1, _BLOCK_T), lambda i: (0, i))
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    with telemetry.stage_scope("megakernel.facility.pallas"):
        acc = pl.pallas_call(
            kern,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((8, _BLOCK_T), lambda i: (0, i)),
                      trow(), trow(), trow(), trow(), smem(), smem()],
            out_specs=pl.BlockSpec((1, _LANE), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, _LANE), jnp.float32),
            interpret=interpret,
        )(dense, *qrows, meta, params)

    totals = {
        "op_carbon": acc[0, _A_GRID_CI] * dt / 1000.0,
        "grid_energy": acc[0, _A_GRID] * dt,
        "dc_energy": (acc[0, _A_IT] + acc[0, _A_COOL]) * dt,
        "it_energy": acc[0, _A_IT] * dt,
        "peak_power": acc[0, _A_GRID_MAX],
        "batt_discharged": acc[0, _A_DK] * dt,
        "cooling_energy": acc[0, _A_COOL] * dt,
        "water_l": acc[0, _A_WATER] * dt,
        "heat_reuse": acc[0, _A_HEAT] * dt,
        "pv_energy": acc[0, _A_PV] * dt,
        "export_energy": acc[0, _A_EXP] * dt,
        "curtailed_energy": acc[0, _A_CUR] * dt,
        "soc_final": acc[0, _A_SOC],
        "was_charging": acc[0, _A_WASC] > 0.5,
    }
    if cfg.pricing.enabled:
        totals["energy_cost"] = acc[0, _A_GRID_PR] * dt
        totals["demand_cost"] = acc[0, _A_DEMAND]
        totals["window_peak_kw"] = acc[0, _A_WPEAK]
        if cfg.renewables.enabled:
            totals["export_revenue"] = (
                acc[0, _A_EXP_PR] * dt
                * jnp.float32(cfg.pricing.export_price_fraction))
    return totals
