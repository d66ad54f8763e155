"""jit'd public wrappers for the Pallas kernels (the ops layer).

Each op dispatches to the Pallas kernel with the pure-jnp oracle available
in kernels/ref.py for testing; the placement kernel's oracle is the
scheduler's own loop, `core.scheduler.first_fit_loop`.  Interpret mode is
resolved PER CALL from the platform the call runs on (`resolved_interpret`,
which reads `core.device.call_platform`): on CPU the kernel body executes
as traced jnp for validation; on TPU/GPU the real Mosaic kernel runs.
Nothing else selects it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import first_fit as _first_fit
from . import fused_step as _fused_step
from . import power_carbon as _power_carbon
from . import ssd_chunk as _ssd_chunk
from repro.core import device, scheduler, telemetry
from repro.core.config import CoolingConfig, PowerModelConfig


def resolved_interpret() -> bool:
    """Should Pallas kernels run in interpret mode?

    Exactly when the platform the call runs on is CPU
    (`device.call_platform`: the device pinned by `jax.default_device(...)`
    if one is, otherwise the default backend), resolved at call time.
    """
    interp = device.call_platform() == "cpu"
    # observability hook: an active telemetry session records how the call
    # resolved (RunRecord.pallas_interpret); no-op — one attr set — when a
    # session is on, free when off
    telemetry.note_pallas_interpret(interp)
    return interp


def host_power(cpu_util, gpu_util, n_gpus, on, cpu_cfg: PowerModelConfig,
               gpu_cfg: PowerModelConfig):
    """Fused utilization->power for the STEAM engine (power only)."""
    p, _, _ = _power_carbon.fused_power_carbon(
        cpu_util, gpu_util, n_gpus, on, 0.0, 0.0,
        cpu_idle=cpu_cfg.idle_w, cpu_max=cpu_cfg.max_w, cpu_curve=cpu_cfg.model,
        gpu_idle=gpu_cfg.idle_w, gpu_max=gpu_cfg.max_w, gpu_curve=gpu_cfg.model,
        interpret=resolved_interpret())
    return p


def facility_power(cpu_util, gpu_util, n_gpus, on, wet_bulb_c, setpoint_c,
                   cpu_cfg: PowerModelConfig, gpu_cfg: PowerModelConfig,
                   cooling_cfg: CoolingConfig):
    """(power_kw[H], it_power_kw, cooling_kw, water_l_per_h) in one VMEM pass.

    The facility-power sibling of `host_power`: the host-axis reduction and
    the weather-driven cooling tail (core/thermal.py) fuse into one kernel,
    so the engine's power+cooling stages leave only four values in HBM.
    """
    return _power_carbon.fused_facility_power(
        cpu_util, gpu_util, n_gpus, on, wet_bulb_c, setpoint_c,
        cpu_idle=cpu_cfg.idle_w, cpu_max=cpu_cfg.max_w, cpu_curve=cpu_cfg.model,
        gpu_idle=gpu_cfg.idle_w, gpu_max=gpu_cfg.max_w, gpu_curve=gpu_cfg.model,
        econ_range=cooling_cfg.economizer_range_c,
        tower_approach=cooling_cfg.tower_approach_c,
        condenser_lift=cooling_cfg.condenser_lift_c,
        carnot_eff=cooling_cfg.carnot_efficiency,
        max_cop=cooling_cfg.max_cop,
        fan_overhead=cooling_cfg.fan_pump_overhead,
        evap_l_per_kwh=cooling_cfg.evap_l_per_kwh_heat,
        interpret=resolved_interpret())


def facility_power_batched(cpu_util, gpu_util, n_gpus, on, wet_bulb_c,
                           setpoint_c, cpu_cfg: PowerModelConfig,
                           gpu_cfg: PowerModelConfig,
                           cooling_cfg: CoolingConfig):
    """Fleet-batched `facility_power`: every input carries a leading region
    axis (utilizations [R, H], weather/setpoint [R]); returns
    (power_kw[R, H], it_kw[R], cooling_kw[R], water_l_per_h[R]).

    This is the batched facility-power path the fleet engine exercises when
    `cfg.use_pallas` is set: `jax.vmap` lowers the kernel's pallas_call
    through its batching rule (one fused program, the region axis folded
    into the grid) rather than looping R kernel launches.  Kept as a public
    op so the batched lowering is pinned by tests/test_kernels.py.
    """
    return jax.vmap(
        lambda cu, gu, ng, o, wb, sp: facility_power(
            cu, gu, ng, o, wb, sp, cpu_cfg, gpu_cfg, cooling_cfg)
    )(cpu_util, gpu_util, n_gpus, on, wet_bulb_c, setpoint_c)


def fused_power_carbon(cpu_util, gpu_util, n_gpus, on, ci, dt_h,
                       cpu_cfg: PowerModelConfig, gpu_cfg: PowerModelConfig):
    """(power_kw[H], dc_power_kw, op_carbon_kg) in one VMEM pass."""
    return _power_carbon.fused_power_carbon(
        cpu_util, gpu_util, n_gpus, on, ci, dt_h,
        cpu_idle=cpu_cfg.idle_w, cpu_max=cpu_cfg.max_w, cpu_curve=cpu_cfg.model,
        gpu_idle=gpu_cfg.idle_w, gpu_max=gpu_cfg.max_w, gpu_curve=gpu_cfg.model,
        interpret=resolved_interpret())


def facility_totals(it_kw, ci, wet_bulb_c, price, price_lo, price_hi, pv_cf,
                    batt_threshold, ci_rising, cfg, **kwargs):
    """The megakernel's facility chain as ONE time-blocked kernel
    (kernels/fused_step.py); returns the run-totals dict."""
    return _fused_step.fused_facility_totals(
        it_kw, ci, wet_bulb_c, price, price_lo, price_hi, pv_cf,
        batt_threshold, ci_rising, cfg, interpret=resolved_interpret(),
        **kwargs)


def first_fit(need_c, need_g, suf_c, suf_g, n_valid, free_c, free_g, usable,
              host_order=None):
    """The scheduler's placement loop as one kernel launch
    (kernels/first_fit.py): `(sel_host i32[k], iters i32)`, bitwise
    `scheduler.first_fit_loop` of the same arguments.

    A call batched by `vmap` runs `first_fit_loop` instead: the vmapped
    `while_loop` runs every lane at once, for as many iterations as the
    busiest lane needs, where the kernel batched over lanes would run them
    one after another.  The form that ran is noted for the run record.
    """
    args = (need_c, need_g, suf_c, suf_g, n_valid, free_c, free_g, usable)
    if host_order is not None:
        args += (host_order,)

    @jax.custom_batching.custom_vmap
    def place(*args):
        telemetry.note_first_fit("pallas")
        rank = None
        if host_order is not None:   # the preference order's inverse
            *args, order = args
            rank = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
        return _first_fit.first_fit(*args, rank,
                                    interpret=resolved_interpret())

    @place.def_vmap
    def _batched(axis_size, in_batched, *args):
        telemetry.note_first_fit("while_loop")
        out = jax.vmap(scheduler.first_fit_loop,
                       in_axes=tuple(0 if b else None for b in in_batched),
                       axis_size=axis_size)(*args)
        return out, (True, True)

    return place(*args)


def ssd_intra_chunk(xdt, da, b, c):
    """Mamba-2 SSD intra-chunk quadratic form (see kernels/ssd_chunk.py)."""
    return _ssd_chunk.ssd_intra_chunk(xdt, da, b, c, interpret=resolved_interpret())


def flash_attention(q, k, v, *, scale, causal=True, block_q=256, block_k=256):
    """Fused online-softmax attention (see kernels/flash_attn.py)."""
    from . import flash_attn as _fa
    return _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=resolved_interpret())
