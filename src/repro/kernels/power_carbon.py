"""Fused power+carbon Pallas kernel — the simulator's per-step hot loop.

The STEAM sweep spends its time in: host utilization -> power model -> sum ->
carbon multiply, executed S times per scenario and vmapped over thousands of
scenarios.  Naively that materializes power[H] to HBM each step.  This kernel
fuses curve evaluation, the host-axis reduction, and the carbon multiply in
VMEM: hosts are tiled (8, 128) (VPU lane-aligned), partial sums accumulate in
the output block across the sequential TPU grid, and only two scalars leave
the core.

Targets TPU (pl.pallas_call + BlockSpec); validated in interpret mode on CPU
against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_SUBLANE = 8
_BLOCK_H = _LANE * _SUBLANE  # hosts per grid step

_CURVES = {
    "linear": lambda u: u,
    "sqrt": lambda u: jnp.sqrt(u),
    "square": lambda u: u * u,
    "cubic": lambda u: u * u * u,
}


def _power_block(cpu_ref, gpu_ref, ngpu_ref, on_ref, *,
                 cpu_idle, cpu_max, cpu_curve, gpu_idle, gpu_max, gpu_curve):
    """The shared per-tile power-curve evaluation (kW block) of both kernels."""
    cpu_u = jnp.clip(cpu_ref[...], 0.0, 1.0)
    gpu_u = jnp.clip(gpu_ref[...], 0.0, 1.0)
    p_cpu = cpu_idle + (cpu_max - cpu_idle) * _CURVES[cpu_curve](cpu_u)
    p_gpu = ((gpu_idle + (gpu_max - gpu_idle) * _CURVES[gpu_curve](gpu_u))
             * ngpu_ref[...])
    return (p_cpu + p_gpu) * on_ref[...] / 1000.0


def _pad_hosts(x, h: int, hp: int, fill: float = 0.0):
    """Pad a host vector f32[h] to the tile grid and fold to [hp/LANE, LANE]."""
    x = jnp.asarray(x, jnp.float32)
    return jnp.pad(x, (0, hp - h), constant_values=fill).reshape(
        hp // _LANE, _LANE)


def _smem():
    """Whole-array block in scalar memory: the kernels' scalar inputs and
    outputs live there (a TPU cannot read or write scalars in VMEM)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _host_specs():
    """(in_specs, power out_spec) shared by both fused kernels: four tiled
    host vectors plus one SMEM block of scalars."""
    tile = lambda: pl.BlockSpec((_SUBLANE, _LANE), lambda i: (i, 0))
    return [tile(), tile(), tile(), tile(), _smem()], tile()


def _kernel(cpu_ref, gpu_ref, ngpu_ref, on_ref, scal_ref,
            power_ref, dc_ref, carbon_ref, *,
            cpu_idle, cpu_max, cpu_curve, gpu_idle, gpu_max, gpu_curve):
    i = pl.program_id(0)
    p_kw = _power_block(cpu_ref, gpu_ref, ngpu_ref, on_ref,
                        cpu_idle=cpu_idle, cpu_max=cpu_max,
                        cpu_curve=cpu_curve, gpu_idle=gpu_idle,
                        gpu_max=gpu_max, gpu_curve=gpu_curve)
    power_ref[...] = p_kw

    ci = scal_ref[0, 0]
    dt = scal_ref[0, 1]
    partial = jnp.sum(p_kw)

    @pl.when(i == 0)
    def _init():
        dc_ref[0, 0] = 0.0
        carbon_ref[0, 0] = 0.0

    dc_ref[0, 0] += partial
    carbon_ref[0, 0] += partial * dt * ci / 1000.0


def _facility_kernel(cpu_ref, gpu_ref, ngpu_ref, on_ref, scal_ref,
                     power_ref, it_ref, cool_ref, water_ref, *,
                     cpu_idle, cpu_max, cpu_curve, gpu_idle, gpu_max,
                     gpu_curve, econ_range, tower_approach, condenser_lift,
                     carnot_eff, max_cop, fan_overhead, evap_l_per_kwh):
    """Per-host power + IT-sum + weather-driven cooling in one VMEM pass.

    Hosts tile over the sequential grid exactly as in `_kernel`; the cooling
    tail (scalar math on the accumulated IT total, the wet-bulb temperature
    and the setpoint) runs once on the LAST grid step, when the host-axis
    reduction is complete — mirroring core/thermal.py term for term.
    """
    i = pl.program_id(0)
    p_kw = _power_block(cpu_ref, gpu_ref, ngpu_ref, on_ref,
                        cpu_idle=cpu_idle, cpu_max=cpu_max,
                        cpu_curve=cpu_curve, gpu_idle=gpu_idle,
                        gpu_max=gpu_max, gpu_curve=gpu_curve)
    power_ref[...] = p_kw

    @pl.when(i == 0)
    def _init():
        it_ref[0, 0] = 0.0
        cool_ref[0, 0] = 0.0
        water_ref[0, 0] = 0.0

    it_ref[0, 0] += jnp.sum(p_kw)

    @pl.when(i == pl.num_programs(0) - 1)
    def _cooling_tail():
        it = it_ref[0, 0]
        wb = scal_ref[0, 0]
        sp = scal_ref[0, 1]
        rng = jnp.maximum(jnp.float32(econ_range), 1e-6)
        frac = jnp.clip((wb - (sp - rng)) / rng, 0.0, 1.0)
        lift = jnp.maximum(wb + tower_approach + condenser_lift - sp,
                           jnp.float32(1.0))
        cop = jnp.clip(carnot_eff * (sp + 273.15) / lift, 1.0, max_cop)
        chiller_kw = frac * it / cop
        cool_ref[0, 0] = fan_overhead * it + chiller_kw
        water_ref[0, 0] = (frac * it + chiller_kw) * evap_l_per_kwh


@functools.partial(
    jax.jit,
    static_argnames=("cpu_idle", "cpu_max", "cpu_curve", "gpu_idle", "gpu_max",
                     "gpu_curve", "econ_range", "tower_approach",
                     "condenser_lift", "carnot_eff", "max_cop", "fan_overhead",
                     "evap_l_per_kwh", "interpret"))
def fused_facility_power(cpu_util, gpu_util, n_gpus, on, wet_bulb_c,
                         setpoint_c, *,
                         cpu_idle: float, cpu_max: float, cpu_curve: str,
                         gpu_idle: float, gpu_max: float, gpu_curve: str,
                         econ_range: float, tower_approach: float,
                         condenser_lift: float, carnot_eff: float,
                         max_cop: float, fan_overhead: float,
                         evap_l_per_kwh: float, interpret: bool = True):
    """Returns (power_kw[H], it_power_kw, cooling_kw, water_l_per_h).

    Like `fused_power_carbon` but the scalar tail is the thermal model of
    core/thermal.py instead of the carbon multiply: cooling power and tower
    evaporation leave the core alongside the per-host power and the IT sum.
    `wet_bulb_c` / `setpoint_c` are traced scalars (sweepable per step/grid).
    """
    h = cpu_util.shape[0]
    hp = max(-(-h // _BLOCK_H) * _BLOCK_H, _BLOCK_H)
    scal = jnp.stack([jnp.asarray(wet_bulb_c, jnp.float32),
                      jnp.asarray(setpoint_c, jnp.float32)]).reshape(1, 2)
    kern = functools.partial(
        _facility_kernel, cpu_idle=cpu_idle, cpu_max=cpu_max,
        cpu_curve=cpu_curve, gpu_idle=gpu_idle, gpu_max=gpu_max,
        gpu_curve=gpu_curve, econ_range=econ_range,
        tower_approach=tower_approach, condenser_lift=condenser_lift,
        carnot_eff=carnot_eff, max_cop=max_cop, fan_overhead=fan_overhead,
        evap_l_per_kwh=evap_l_per_kwh)
    in_specs, power_spec = _host_specs()
    power, it, cool, water = pl.pallas_call(
        kern,
        grid=(hp // _BLOCK_H,),
        in_specs=in_specs,
        out_specs=[power_spec, _smem(), _smem(), _smem()],
        out_shape=[
            jax.ShapeDtypeStruct((hp // _LANE, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_pad_hosts(cpu_util, h, hp), _pad_hosts(gpu_util, h, hp),
      _pad_hosts(n_gpus, h, hp), _pad_hosts(on, h, hp), scal)
    return power.reshape(-1)[:h], it[0, 0], cool[0, 0], water[0, 0]


@functools.partial(
    jax.jit,
    static_argnames=("cpu_idle", "cpu_max", "cpu_curve", "gpu_idle", "gpu_max",
                     "gpu_curve", "interpret"))
def fused_power_carbon(cpu_util, gpu_util, n_gpus, on, ci, dt_h, *,
                       cpu_idle: float, cpu_max: float, cpu_curve: str,
                       gpu_idle: float, gpu_max: float, gpu_curve: str,
                       interpret: bool = True):
    """Returns (power_kw[H], dc_power_kw scalar, op_carbon_kg scalar).

    All inputs f32[H] except ci/dt_h scalars.  H is padded to the 1024-host
    tile internally; padding rows have on=0 so they contribute nothing.
    """
    h = cpu_util.shape[0]
    hp = max(-(-h // _BLOCK_H) * _BLOCK_H, _BLOCK_H)
    scal = jnp.stack([jnp.asarray(ci, jnp.float32),
                      jnp.asarray(dt_h, jnp.float32)]).reshape(1, 2)
    kern = functools.partial(
        _kernel, cpu_idle=cpu_idle, cpu_max=cpu_max, cpu_curve=cpu_curve,
        gpu_idle=gpu_idle, gpu_max=gpu_max, gpu_curve=gpu_curve)
    in_specs, power_spec = _host_specs()
    power, dc, carbon = pl.pallas_call(
        kern,
        grid=(hp // _BLOCK_H,),
        in_specs=in_specs,
        out_specs=[power_spec, _smem(), _smem()],
        out_shape=[
            jax.ShapeDtypeStruct((hp // _LANE, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_pad_hosts(cpu_util, h, hp), _pad_hosts(gpu_util, h, hp),
      _pad_hosts(n_gpus, h, hp), _pad_hosts(on, h, hp), scal)
    return power.reshape(-1)[:h], dc[0, 0], carbon[0, 0]
