"""Dense simulation state for the tensorized STEAM engine.

OpenDC-STEAM models a datacenter as an object graph traversed by events.  On a
TPU that shape is hostile (pointer chasing, data-dependent control flow), so the
state here is struct-of-arrays: a padded task table, a host table, and scalar
battery/accumulator state.  Every stage of the engine is a pure function over
these pytrees; `lax.scan` drives the timeline and `vmap` drives scenario
parallelism.  All times are hours (f32), energy kWh, power kW, carbon kgCO2-eq.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

# Task status codes (i32).  PENDING covers never-started, shifted, stopped and
# failure-requeued tasks alike: the scheduler only looks at eligibility.
PENDING = 0
RUNNING = 1
DONE = 2
INVALID = 3  # padding rows

# Job-class codes (i32), ordered by default scheduling priority (low to
# high).  BATCH is the legacy default: tables built without class columns
# are all-batch / all-shiftable / config-grace and reproduce the pre-typed
# pipeline bit-for-bit.  INTERACTIVE models latency-bound inference traffic:
# top priority, non-shiftable (it bypasses the temporal-shifting gate), and
# a tight per-task SLA grace.
JOB_BATCH = 0
JOB_TRAINING = 1
JOB_INTERACTIVE = 2
N_JOB_CLASSES = 3
JOB_CLASS_NAMES = ("batch", "training", "interactive")

_INF = jnp.float32(jnp.inf)


def active_host_mask(n_hosts: int, n_active) -> jax.Array:
    """bool[n_hosts] marking the first `n_active` hosts as provisioned.

    `n_active` may be a python int OR a traced scalar, which is what lets
    horizontal scaling be a scenario-grid axis (core/grid.py) rather than a
    recompile."""
    return jnp.arange(n_hosts) < n_active


class TaskTable(NamedTuple):
    """Padded struct-of-arrays task table, pre-sorted by arrival time.

    Pre-sorting by arrival makes FIFO priority the row order, which lets the
    scheduler select "first K eligible" with a cumsum instead of a per-step
    argsort (see core/scheduler.py).
    """

    arrival: jax.Array        # f32[T] hours; +inf for padding rows
    duration: jax.Array       # f32[T] nominal runtime at full speed
    remaining: jax.Array      # f32[T] remaining runtime
    ckpt_remaining: jax.Array # f32[T] remaining at the last checkpoint
    cores: jax.Array          # f32[T] CPU cores required
    gpus: jax.Array           # f32[T] GPUs required (0 for CPU-only tasks)
    cpu_util: jax.Array       # f32[T] utilization of allocated cores while running
    gpu_util: jax.Array       # f32[T] utilization of allocated GPUs while running
    status: jax.Array         # i32[T]
    host: jax.Array           # i32[T]; -1 when not placed
    first_start: jax.Array    # f32[T]; +inf until first scheduled
    finish: jax.Array         # f32[T]; +inf until done
    lost_work: jax.Array      # f32[T] hours of work redone due to failures
    job_class: jax.Array      # i32[T] JOB_* code (batch/training/interactive)
    priority: jax.Array       # i32[T] scheduling priority, higher first
    shiftable: jax.Array      # bool[T] may temporal shifting delay/pause it?
    sla_grace: jax.Array      # f32[T] per-task SLA grace hours; <0 = cfg default
    speed: jax.Array          # f32[T] speed of the host a RUNNING task was
                              #   placed on, written at placement (1.0 before)

    @property
    def n(self) -> int:
        return self.arrival.shape[0]


# The value of every column in an empty row: a padding row
# (`pad_task_table`) and a row a fleet spill vacates
# (`resilience.cross_region_spill`).
EMPTY_TASK_ROW = TaskTable(
    arrival=float("inf"), duration=0.0, remaining=0.0, ckpt_remaining=0.0,
    cores=0.0, gpus=0.0, cpu_util=0.0, gpu_util=0.0, status=INVALID, host=-1,
    first_start=float("inf"), finish=float("inf"), lost_work=0.0,
    job_class=JOB_BATCH, priority=0, shiftable=True, sla_grace=-1.0,
    speed=1.0)


class HostTable(NamedTuple):
    """Host inventory.  `active` is the horizontal-scaling mask (fixed during
    a run, but it may be built from a *traced* host count — see
    `active_host_mask` / dyn ctx key `n_active_hosts` — so scenario grids can
    sweep the scaling level); `up` tracks failures.  Free capacity is
    recomputed from the task table each step (robust against any interrupt
    path forgetting to release)."""

    cores: jax.Array   # f32[H] total CPU cores per host
    n_gpus: jax.Array  # f32[H] GPUs per host
    active: jax.Array  # bool[H] provisioned by horizontal scaling
    up: jax.Array      # bool[H] not currently failed
    repair_at: jax.Array  # f32[H] absolute hour when a failed host recovers
    speed: jax.Array   # f32[H] execution-speed factor (<1 = straggler host)


class BatteryState(NamedTuple):
    charge: jax.Array       # f32[] kWh currently stored
    was_charging: jax.Array # bool[] hysteresis memory for the trough-wait rule


class MetricsAcc(NamedTuple):
    op_carbon: jax.Array       # f32[] kg CO2 from grid energy
    emb_carbon: jax.Array      # f32[] kg CO2 embodied (hosts + battery share)
    grid_energy: jax.Array     # f32[] kWh drawn from the grid
    dc_energy: jax.Array       # f32[] kWh facility total (IT + cooling)
    it_energy: jax.Array       # f32[] kWh consumed by the IT equipment
    cooling_energy: jax.Array  # f32[] kWh consumed by cooling (0 if disabled)
    water_l: jax.Array         # f32[] litres evaporated by the cooling tower
    peak_power: jax.Array      # f32[] kW max grid draw
    batt_discharged: jax.Array # f32[] kWh served from the battery
    n_interrupts: jax.Array    # f32[] failure interruptions (work rolled back)
    n_shift_delays: jax.Array  # f32[] task-steps spent delayed by shifting
    energy_cost: jax.Array     # f32[] currency; 0 unless cfg.pricing.enabled
    demand_cost: jax.Array     # f32[] currency from CLOSED billing windows
    window_peak_kw: jax.Array  # f32[] running peak of the open billing window
    pv_energy: jax.Array       # f32[] kWh generated on-site (renewables)
    export_energy: jax.Array   # f32[] kWh of surplus exported to the grid
    curtailed_energy: jax.Array  # f32[] kWh of surplus thrown away
    export_revenue: jax.Array  # f32[] currency earned by the export tariff
    heat_reuse: jax.Array      # f32[] kWh of chiller-path heat reclaimed
    n_stops: jax.Array         # f32[] graceful shifting pauses (subset context
                               #   of n_interrupts; NOT failure interrupts)
    throttled_h: jax.Array     # f32[] hours spent thermally throttled
    derate_h: jax.Array        # f32[] hours with chiller/PDU derated
    n_spills: jax.Array        # f32[] tasks spilled to another region (fleet)
    first_fit_iters: jax.Array   # f32[] first-fit placement-loop iterations
    first_fit_placed: jax.Array  # f32[] ... of which placed a task
    slot_bound_steps: jax.Array  # f32[] steps with more eligible tasks
                                 #   than the placement bound


class SimState(NamedTuple):
    t: jax.Array          # f32[] current time in hours
    step: jax.Array       # i32[] current step index
    tasks: TaskTable
    hosts: HostTable
    battery: BatteryState
    metrics: MetricsAcc
    rng: jax.Array        # PRNG key for stochastic failures
    # opt-in probe-bus ring buffer (telemetry.Probes); None when
    # cfg.probes.enabled is False — a leafless pytree node, so the scan
    # carry, jit signatures and golden outputs are unchanged by default
    probes: Any = None
    # thermal-throttle factor applied to hosts THIS step, computed from the
    # PREVIOUS step's facility state (core/resilience.py).  None when
    # cfg.resilience.enabled is False — same leafless-node trick as probes,
    # so the disabled engine is structurally (and bitwise) unchanged
    throttle: Any = None


def make_task_table(arrival, duration, cores, gpus=None, cpu_util=None,
                    gpu_util=None, job_class=None, priority=None,
                    shiftable=None, sla_grace=None) -> TaskTable:
    """Build a task table from per-task arrays; sorts by arrival (FIFO order).

    The typed-workload columns default to the legacy homogeneous table:
    all-batch (`job_class` zeros), priority = class code, shiftable for
    every non-interactive class, and `sla_grace` -1 (sentinel: use
    cfg.sla_grace_h).
    """
    arrival = jnp.asarray(arrival, jnp.float32)
    duration = jnp.asarray(duration, jnp.float32)
    cores = jnp.asarray(cores, jnp.float32)
    t = arrival.shape[0]
    gpus = jnp.zeros(t, jnp.float32) if gpus is None else jnp.asarray(gpus, jnp.float32)
    cpu_util = (jnp.ones(t, jnp.float32) if cpu_util is None
                else jnp.asarray(cpu_util, jnp.float32))
    gpu_util = (jnp.where(gpus > 0, 1.0, 0.0).astype(jnp.float32) if gpu_util is None
                else jnp.asarray(gpu_util, jnp.float32))
    job_class = (jnp.zeros(t, jnp.int32) if job_class is None
                 else jnp.asarray(job_class, jnp.int32))
    priority = (job_class if priority is None
                else jnp.asarray(priority, jnp.int32))
    shiftable = (job_class != JOB_INTERACTIVE if shiftable is None
                 else jnp.asarray(shiftable, bool))
    sla_grace = (jnp.full(t, -1.0, jnp.float32) if sla_grace is None
                 else jnp.asarray(sla_grace, jnp.float32))
    order = jnp.argsort(arrival)
    arrival, duration, cores = arrival[order], duration[order], cores[order]
    gpus, cpu_util, gpu_util = gpus[order], cpu_util[order], gpu_util[order]
    job_class, priority = job_class[order], priority[order]
    shiftable, sla_grace = shiftable[order], sla_grace[order]
    inf = jnp.full(t, _INF)
    return TaskTable(
        arrival=arrival, duration=duration, remaining=duration,
        ckpt_remaining=duration, cores=cores, gpus=gpus,
        cpu_util=cpu_util, gpu_util=gpu_util,
        status=jnp.where(jnp.isfinite(arrival), PENDING, INVALID).astype(jnp.int32),
        host=jnp.full(t, -1, jnp.int32), first_start=inf, finish=inf,
        lost_work=jnp.zeros(t, jnp.float32),
        job_class=job_class, priority=priority, shiftable=shiftable,
        sla_grace=sla_grace, speed=jnp.ones(t, jnp.float32),
    )


def with_interactive_frac(tasks: TaskTable, frac, grace_h,
                          seed: int = 0) -> TaskTable:
    """Re-type a `frac` share of tasks as interactive inference.

    Backs the `interactive_frac` dyn key (core/grid.py): `frac` may be a
    TRACED scalar, so a scenario grid can sweep the interactive share inside
    one compiled program.  Each task draws a fixed uniform (from `seed`, NOT
    from `frac`), and tasks with u < frac flip to JOB_INTERACTIVE — top
    priority, non-shiftable, `grace_h` SLA grace, and the interactive power
    profile (core/power.py class tables).  Fixing the per-task draws makes
    the selection nested across frac levels: raising frac only ADDS
    interactive tasks.  frac == 0.0 leaves every column's values unchanged.
    """
    from .power import class_utilization  # late: power imports nothing back
    u = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(seed), 7),
                           (tasks.n,))
    inter = (u < frac) & (tasks.status != INVALID)
    cls = jnp.where(inter, JOB_INTERACTIVE, tasks.job_class).astype(jnp.int32)
    cpu_c, gpu_c = class_utilization(cls)
    return tasks._replace(
        job_class=cls,
        priority=jnp.where(inter, JOB_INTERACTIVE,
                           tasks.priority).astype(jnp.int32),
        shiftable=tasks.shiftable & ~inter,
        sla_grace=jnp.where(inter, jnp.float32(grace_h), tasks.sla_grace),
        cpu_util=jnp.where(inter, cpu_c, tasks.cpu_util),
        gpu_util=jnp.where(inter, jnp.where(tasks.gpus > 0, gpu_c, 0.0),
                           tasks.gpu_util),
    )


def retime_task_table(tasks: TaskTable, arrival) -> TaskTable:
    """Replace the arrival column with a pre-sorted (possibly traced) one.

    Backs the `arrival_trace` dyn key (core/grid.py `tasktrace_axis`): each
    grid point re-times the SAME task population with arrivals sampled from
    a different traffic curve (tasktraces/synthetic.py).  Rows must already
    be ascending — the axis constructor sorts host-side, because an argsort
    inside the compiled cell would also have to re-pair every other column.
    Non-finite arrivals mark the row INVALID (and vice versa), like
    `make_task_table`.
    """
    arrival = jnp.asarray(arrival, jnp.float32)
    status = jnp.where(jnp.isfinite(arrival), PENDING, INVALID)
    return tasks._replace(arrival=arrival, status=status.astype(jnp.int32))


def priority_schedule_order(tasks: TaskTable, levels: int) -> jax.Array:
    """Stable permutation sorting rows into (priority desc, arrival) order.

    The scheduler's merged admission order for priority classes is
    "higher level first, FIFO within a level".  Rows are already
    arrival-sorted, so the stable composite key
    `(levels-1-priority) * T + row` makes that merged order the ROW order —
    selection then degenerates to the plain FIFO prefix scan
    (`scheduler._first_k_indices`) instead of a level-major `[L*T]`
    flatten+cumsum EVERY step of the demand scan.  The permutation is
    computed once per simulation, outside the scan; `priority` may be
    traced (dyn `interactive_frac`), so this stays jit/vmap-safe.  INVALID
    padding rows carry priority 0 and sit at the tail of the arrival
    order, so they stay at the very end of the permuted table.
    """
    t = tasks.n
    prio = jnp.clip(jnp.asarray(tasks.priority).astype(jnp.int32), 0,
                    levels - 1)
    key = (jnp.int32(levels - 1) - prio) * jnp.int32(t) + jnp.arange(
        t, dtype=jnp.int32)
    return jnp.argsort(key).astype(jnp.int32)


def permute_task_table(tasks: TaskTable, order) -> TaskTable:
    """Reorder every column of the table by `order` (i32[T] permutation).

    Invert with `permute_task_table(t, inverse_permutation(order))`.
    """
    return jax.tree.map(lambda col: col[order], tasks)


def inverse_permutation(order) -> jax.Array:
    """Inverse of a permutation vector: inv[order[i]] = i."""
    return jnp.argsort(order).astype(jnp.int32)


def stack_task_tables(tables) -> TaskTable:
    """Stack equal-width task tables along a new leading region/batch axis.

    The result [R, W] is what `jax.vmap(simulate)` consumes — the fleet
    engine (core/fleet.py) and spatial splitting (core/spatial.py) both
    batch per-region sub-workloads this way."""
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                        *tables)


def pad_task_table(tasks: TaskTable, n: int) -> TaskTable:
    """Pad a task table to n rows with `EMPTY_TASK_ROW` (for batching)."""
    t = tasks.n
    if t == n:
        return tasks
    assert t < n, f"cannot shrink task table {t} -> {n}"
    return jax.tree.map(
        lambda col, fill: jnp.concatenate(
            [col, jnp.full((n - t,), fill, col.dtype)]),
        tasks, EMPTY_TASK_ROW)


def make_host_table(n_hosts: int, cores_per_host: float, gpus_per_host: float = 0.0,
                    n_active: int | None = None,
                    straggler_frac: float = 0.0,
                    straggler_speed: float = 0.5,
                    seed: int = 0) -> HostTable:
    """Homogeneous host inventory; `n_active` < n_hosts models horizontal
    down-scaling (the remaining hosts are powered off entirely).

    straggler_frac > 0 marks that fraction of hosts as STRAGGLERS running at
    `straggler_speed` x nominal — the operational phenomenon (degraded disks,
    thermal throttling, noisy neighbours) that inflates task durations and
    SLA violations; a datacenter mitigates by over-provisioning (horizontal
    scaling interacts!) or draining, both expressible here."""
    n_active = n_hosts if n_active is None else n_active
    speed = jnp.ones(n_hosts, jnp.float32)
    if straggler_frac > 0.0:
        k = jax.random.PRNGKey(seed)
        slow = jax.random.uniform(k, (n_hosts,)) < straggler_frac
        speed = jnp.where(slow, straggler_speed, 1.0).astype(jnp.float32)
    return HostTable(
        cores=jnp.full(n_hosts, cores_per_host, jnp.float32),
        n_gpus=jnp.full(n_hosts, gpus_per_host, jnp.float32),
        active=active_host_mask(n_hosts, n_active),
        up=jnp.ones(n_hosts, bool),
        repair_at=jnp.zeros(n_hosts, jnp.float32),
        speed=speed,
    )


def init_battery() -> BatteryState:
    return BatteryState(charge=jnp.float32(0.0), was_charging=jnp.array(False))


def init_metrics() -> MetricsAcc:
    z = jnp.float32(0.0)
    return MetricsAcc(op_carbon=z, emb_carbon=z, grid_energy=z, dc_energy=z,
                      it_energy=z, cooling_energy=z, water_l=z,
                      peak_power=z, batt_discharged=z, n_interrupts=z,
                      n_shift_delays=z, energy_cost=z, demand_cost=z,
                      window_peak_kw=z, pv_energy=z, export_energy=z,
                      curtailed_energy=z, export_revenue=z, heat_reuse=z,
                      n_stops=z, throttled_h=z, derate_h=z, n_spills=z,
                      first_fit_iters=z, first_fit_placed=z,
                      slot_bound_steps=z)


def init_sim_state(tasks: TaskTable, hosts: HostTable, seed: int = 0) -> SimState:
    return SimState(
        t=jnp.float32(0.0), step=jnp.int32(0), tasks=tasks, hosts=hosts,
        battery=init_battery(), metrics=init_metrics(),
        rng=jax.random.PRNGKey(seed),
    )
