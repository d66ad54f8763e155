"""Closed-loop resilience: facility failures, thermal throttling, reactive placement.

The open-loop engine lets failures touch hosts and lets cooling consume
energy, but nothing ever pushes back: cooling never slows compute, facility
equipment never fails, and placement ignores failure history.  This module
closes three loops (paper §VI-A2, finding F1 — failures erode the savings
of down-scaling), all as pure functions the engine threads through both
backends:

1. **Facility failure injection** (`facility_failure_series`) — memoryless
   chiller-derate and PDU-cap processes with the same MTBF/deterministic-
   repair shape as the host model in core/failures.py.  Crucially the
   processes depend only on the run seed, NOT on simulation state, so they
   are precomputed as exogenous per-step series in `build_step_inputs`:
   both backends consume identical inputs and the megakernel's facility
   half stays vectorized over the horizon.

2. **Thermal throttling feedback** (`inlet_proxy_c` / `next_throttle`) — a
   rack-inlet temperature proxy built from wet-bulb + IT load, divided by
   the chiller derate (degraded cooling runs hotter).  Above the trip
   point the host speed/utilization cap for the NEXT tick drops to
   `throttle_factor`; the one-step delay keeps the recurrence causal
   (throttle at step t is a function of facility state at t-1), which is
   exactly what lets the megakernel carry it through its demand scan.

3. **Failure-reactive placement** (`host_rank` / `cross_region_spill`) —
   the scheduler prefers hosts that are up and longest since their last
   repair, and the fleet executor can move interrupted tasks to the
   healthiest region each step.

Everything here is seed-deterministic and traces cleanly under vmap, so
`failure_hazard_scale` (a dyn key, see core/grid.py) can sweep a healthy
datacenter (scale 0.0: p_fail is exactly 0) against a collapsing one
inside a single compiled grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ResilienceConfig
from .state import (EMPTY_TASK_ROW, INVALID, PENDING, HostTable, MetricsAcc,
                    TaskTable)

# fold_in constants decorrelating the facility processes from the host
# failure stream (which consumes the SimState rng) and from each other
_CHILLER_STREAM = 101
_PDU_STREAM = 103


def _failure_process(key, n_steps: int, dt_h: float, mtbf_h: float,
                     repair_h: float, hazard_scale) -> jax.Array:
    """bool[n_steps] 'derated' flags from a memoryless failure process.

    Matches core/failures.py: per-step failure probability
    ``1 - exp(-hazard * dt / mtbf)`` while healthy, then a deterministic
    repair countdown of ``ceil(repair_h / dt_h)`` steps.  `hazard_scale`
    may be a traced scalar (dyn key `failure_hazard_scale`); 0.0 gives
    p_fail == 0 exactly, i.e. a provably healthy facility in the same
    compiled program.
    """
    u = jax.random.uniform(key, (n_steps,))
    hazard = jnp.asarray(hazard_scale, jnp.float32)
    p_fail = 1.0 - jnp.exp(-hazard * (dt_h / mtbf_h))
    repair_steps = max(int(round(repair_h / dt_h)), 1)

    def body(down, u_t):
        fail = (down == 0) & (u_t < p_fail)
        down = jnp.where(fail, repair_steps, jnp.maximum(down - 1, 0))
        return down, down > 0

    _, derated = jax.lax.scan(body, jnp.int32(0), u)
    return derated


def facility_failure_series(seed, n_steps: int, dt_h: float,
                            cfg: ResilienceConfig, hazard_scale=None):
    """Precompute the exogenous facility failure series for one run.

    Returns ``(chiller_derate f32[n_steps], pdu_cap_scale bool[n_steps])``:
    the per-step COP/economizer scale (1.0 healthy, `cfg.chiller_derate`
    while the chiller is derated) and the per-step PDU-derated flag (the
    engine turns it into a kW clamp using `cfg.pdu_cap_kw` or the
    `pdu_cap_kw` dyn value).  `seed` and `hazard_scale` may both be traced,
    so `seed_axis` and `failure_hazard_scale` grid axes batch over this.
    """
    key = jax.random.PRNGKey(seed)
    hazard = jnp.float32(1.0) if hazard_scale is None else hazard_scale
    chiller_down = _failure_process(
        jax.random.fold_in(key, _CHILLER_STREAM), n_steps, dt_h,
        cfg.chiller_mtbf_h, cfg.chiller_repair_h, hazard)
    pdu_down = _failure_process(
        jax.random.fold_in(key, _PDU_STREAM), n_steps, dt_h,
        cfg.pdu_mtbf_h, cfg.pdu_repair_h, hazard)
    derate = jnp.where(chiller_down, jnp.float32(cfg.chiller_derate),
                       jnp.float32(1.0))
    return derate, pdu_down


def inlet_proxy_c(it_kw, wet_bulb_c, chiller_derate,
                  cfg: ResilienceConfig) -> jax.Array:
    """Rack-inlet temperature proxy (degC).

    ``wet_bulb + approach + load_coeff * it_kw / derate`` — the load term is
    divided by the chiller derate because degraded cooling removes less
    heat per kW, so the same IT load runs hotter.  Deliberately a proxy,
    not a CFD model: it is monotone in load and in cooling degradation,
    which is all the trip rule needs.
    """
    derate = jnp.maximum(jnp.asarray(chiller_derate, jnp.float32), 1e-3)
    return (jnp.asarray(wet_bulb_c, jnp.float32) + cfg.inlet_approach_c
            + cfg.inlet_load_c_per_kw * jnp.asarray(it_kw, jnp.float32) / derate)


def next_throttle(it_kw, raw_it_kw, wet_bulb_c, chiller_derate, pdu_cap_kw,
                  cfg: ResilienceConfig, threshold_c=None) -> jax.Array:
    """Host speed/utilization cap for the NEXT step (f32 scalar in (0, 1]).

    Two caps combine by min:
      * thermal trip — if the inlet proxy at the (capped) IT load exceeds
        `threshold_c` (default `cfg.throttle_inlet_c`; dyn-sweepable), the
        next step runs at `cfg.throttle_factor`;
      * PDU headroom — if the UNCAPPED demand `raw_it_kw` exceeds the PDU
        clamp, next step's utilization is scaled toward the cap, so the
        clamp converges instead of chopping power without slowing work.

    The one-step delay (computed at the end of step t, applied at t+1) is
    what keeps the coupled recurrence causal — and lets the megakernel
    carry a single scalar through its demand scan.
    """
    th = (jnp.float32(cfg.throttle_inlet_c) if threshold_c is None
          else jnp.asarray(threshold_c, jnp.float32))
    inlet = inlet_proxy_c(it_kw, wet_bulb_c, chiller_derate, cfg)
    thermal = jnp.where(inlet > th, jnp.float32(cfg.throttle_factor),
                        jnp.float32(1.0))
    raw = jnp.maximum(jnp.asarray(raw_it_kw, jnp.float32), 1e-6)
    pdu = jnp.clip(jnp.asarray(pdu_cap_kw, jnp.float32) / raw, 0.0, 1.0)
    return jnp.minimum(thermal, pdu)


def host_rank(hosts: HostTable, now) -> jax.Array:
    """i32[H] host preference order for failure-reactive placement.

    Score = time since the host's last repair (hosts that failed recently
    are the riskiest: MTBF is memoryless but repair_at is the only failure
    history the state carries, and recently-repaired hardware correlates
    with ongoing trouble in practice).  Down/inactive hosts sink to the
    bottom.  `argsort` is stable and `repair_at` is 0 for never-failed
    hosts, so with no failure history the order is the identity and
    first-fit placement is bitwise-unchanged.
    """
    usable = hosts.active & hosts.up
    since_repair = jnp.asarray(now, jnp.float32) - hosts.repair_at
    score = jnp.where(usable, since_repair, -jnp.inf)
    return jnp.argsort(-score).astype(jnp.int32)


def cross_region_spill(tasks: TaskTable, hosts: HostTable,
                       metrics: MetricsAcc, max_spills: int):
    """Move up to `max_spills` interrupted tasks to the healthiest region.

    Fleet-level reactive placement (core/fleet.simulate_fleet with
    cfg.resilience.spill_interrupted): all leaves carry a leading region
    axis [R, ...].  A spill candidate is a PENDING task that has already
    started once (finite `first_start` — i.e. it was interrupted by a
    failure or paused by the stopper) in a region strictly less healthy
    than the healthiest one, where health = fraction of provisioned hosts
    currently up.  Each move copies the task row into the first INVALID
    (padding) slot of the target region and resets the source row to
    `state.EMPTY_TASK_ROW`, so task counts stay conserved;
    `metrics.n_spills` counts moves per source region.  With no failures
    every region's health is 1.0, no candidate qualifies, and the tables
    pass through with identical values.
    """
    act = hosts.active.astype(jnp.float32)
    up = (hosts.active & hosts.up).astype(jnp.float32)
    health = jnp.sum(up, axis=1) / jnp.maximum(jnp.sum(act, axis=1), 1.0)
    target = jnp.argmax(health)
    w = tasks.arrival.shape[1]

    def one_move(_, carry):
        tasks, metrics = carry
        cand = ((tasks.status == PENDING) & jnp.isfinite(tasks.first_start)
                & (health < health[target])[:, None])
        flat = cand.reshape(-1)
        src = jnp.argmax(flat)
        r, c = src // w, src % w
        free = tasks.status[target] == INVALID
        slot = jnp.argmax(free)
        do = flat[src] & free[slot]

        def move(col, fill):
            v = col[r, c]
            col = col.at[target, slot].set(
                jnp.where(do, v, col[target, slot]))
            return col.at[r, c].set(
                jnp.where(do, jnp.asarray(fill, col.dtype), v))

        tasks = jax.tree.map(move, tasks, EMPTY_TASK_ROW)
        # the moved row keeps status PENDING at the target (move() copied
        # it), so the target region's scheduler picks it up next step
        metrics = metrics._replace(
            n_spills=metrics.n_spills.at[r].add(
                do.astype(jnp.float32)))
        return tasks, metrics

    tasks, metrics = jax.lax.fori_loop(0, max_spills, one_move,
                                       (tasks, metrics))
    return tasks, metrics
