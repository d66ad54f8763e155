"""Tensorized FIFO scheduling (paper §IV-A resource managers).

OpenDC's scheduler walks an event queue and places each task with first-fit.
The tensorized equivalent exploits one invariant: FIFO priority is arrival
order, and the task table is pre-sorted by arrival, so "the next tasks to
schedule" are simply *the first K eligible rows* — selected with a cumsum
instead of a per-step argsort.  Placement itself is a bounded sequential
loop (first-fit needs sequential core accounting); K bounds work per step and
is exact whenever K >= eligible tasks that can start this step.

Two modes:
  first_fit  — exact greedy placement, the production path.  The placement
               loop is one Pallas kernel launch per step on the TPU
               (kernels/first_fit.py, through `kernels.ops.first_fit`) and
               the `while_loop` of `first_fit_loop` on every other platform
               and under `vmap`; both place bitwise alike.
  aggregate  — capacity-only admission that ignores per-host fragmentation;
               this reproduces the optimistic behaviour of analytical models
               the paper critiques (§III), and is also much cheaper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import device
from . import telemetry as telemetry_mod
from .config import SchedulerConfig
from .state import HostTable, TaskTable, PENDING, RUNNING


# The host CPU's rule: up to this host count the per-host sums run as a
# one-hot matmul, above it as segment_sum.  XLA's CPU scatter costs ~50us
# per call at N=1024, which dominated the scan step (the sums run EVERY
# step, inside the hot loop), while the [h, N] matmul is tens of FLOPs per
# task; above 256 hosts the CPU's matmul work, h*N, stops paying for itself.
# The TPU has no such threshold.  There segment_sum lowers to a scatter-add
# that serializes over the task rows (on a TPU v5e at 93,587 tasks: 0.82 ms
# per column, ~450x the time it takes to read its inputs), while the
# one-hot contraction fuses its iota-compare into one matrix-unit pass and
# never writes the one-hot to HBM.
_MATMUL_MAX_HOSTS = 256


def per_host_sum_form(h: int) -> str:
    """The form the per-host sums take on the platform the call runs on:
    "one_hot" (a contraction with the host one-hot) on the TPU at every
    host count and elsewhere up to `_MATMUL_MAX_HOSTS`, else
    "segment_sum"."""
    if device.call_platform() == "tpu" or h <= _MATMUL_MAX_HOSTS:
        return "one_hot"
    return "segment_sum"


def first_fit_form() -> str:
    """The form the placement loop takes on the platform the call runs on:
    "pallas" (one kernel launch a step, `kernels.ops.first_fit`) on the
    TPU, else "while_loop" (`first_fit_loop`).  A call batched by `vmap`
    runs the `while_loop` on every platform (the door's batching rule)."""
    return "pallas" if device.call_platform() == "tpu" else "while_loop"


def first_fit_loop(need_c, need_g, suf_c, suf_g, n_valid, free_c, free_g,
                   usable, host_order=None):
    """Sequential first-fit of k candidate slots onto H hosts, as a
    `while_loop`: `(sel_host i32[k], iters i32)`, the host each slot took
    (-1 where none) and the iterations run.  The oracle of the Pallas form.

    Slots `[0, n_valid)` are the valid candidates, with needs `need_c`,
    `need_g` and their suffix minima `suf_c`, `suf_g` (+inf past
    `n_valid`).  The loop stops at the first slot where no usable host
    clears those minima: every later slot would be a placement no-op (it
    skips the candidate and changes no capacity), so the outcome is
    bit-for-bit that of running all k.  The minima may come from different
    candidates, so it can run past the last possible placement, but never
    stops before one.  Under vmap the loop runs until every lane's slots
    are done.

    The body records each slot's host in a k-vector (the `[T]` table
    writes happen once, after the loop) and updates the free capacity with
    a select, not a scatter: `free - where(hidx == hj, take, 0.0)` applies
    `x + (-take)` to the chosen host and `x - 0.0` (an IEEE no-op)
    elsewhere, bit-for-bit `.at[hj].add(-take)`.
    """
    k = need_c.shape[0]
    hidx = jnp.arange(free_c.shape[0], dtype=jnp.int32)

    def cond(carry):
        i, fc, fg = carry[0], carry[1], carry[2]
        ii = jnp.minimum(i, k - 1)
        return (i < n_valid) & jnp.any(
            (fc >= suf_c[ii]) & (fg >= suf_g[ii]) & usable)

    def body(carry):
        i, free_c, free_g, sel_host = carry
        need_c_i, need_g_i = need_c[i], need_g[i]
        fits = (free_c >= need_c_i) & (free_g >= need_g_i) & usable
        if host_order is None:
            h = jnp.argmax(fits)        # first host that fits (first-fit)
        else:  # first fitting host in preference order
            h = host_order[jnp.argmax(fits[host_order])]
        placed = fits[h]
        hj = jnp.where(placed, h, 0).astype(jnp.int32)
        take_c = jnp.where(placed, need_c_i, 0.0)
        take_g = jnp.where(placed, need_g_i, 0.0)
        free_c = free_c - jnp.where(hidx == hj, take_c, 0.0)
        free_g = free_g - jnp.where(hidx == hj, take_g, 0.0)
        sel_host = sel_host.at[i].set(
            jnp.where(placed, h.astype(jnp.int32), -1))
        return i + 1, free_c, free_g, sel_host

    iters, _, _, sel_host = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), free_c, free_g, jnp.full((k,), -1, jnp.int32)))
    return sel_host, iters


def _per_host_sum(vals, seg, h: int):
    """`segment_sum(vals, seg, h)` of a `[T, C]` stack of columns: `[H, C]`.

    One call builds the host one-hot once for all C columns.  Exact for
    integer-valued columns (core/GPU counts) in any order; float columns
    differ from segment_sum in summation order only.  HIGHEST precision
    keeps the f32 values f32 on the TPU's matrix unit, whose default rounds
    operands to bf16 (the one-hot itself is exact in bf16).
    """
    form = per_host_sum_form(h)
    telemetry_mod.note_per_host_sum(form)
    if form == "one_hot":
        onehot = seg[None, :] == jnp.arange(h, dtype=seg.dtype)[:, None]
        return jnp.matmul(onehot.astype(vals.dtype), vals,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.ops.segment_sum(vals, seg, h)


def free_capacity(tasks: TaskTable, hosts: HostTable):
    """Recompute per-host free CPU cores and GPUs from the task table."""
    h = hosts.cores.shape[0]
    # both per-host sums' scope, nested in the caller's stage
    with telemetry_mod.stage_scope("stage_per_host_sum"):
        # host >= 0 like failures.interrupt_tasks: the clip below is only
        # index safety — without the mask a RUNNING task carrying host == -1
        # would be silently billed to host 0
        running = (tasks.status == RUNNING) & (tasks.host >= 0)
        seg = jnp.clip(tasks.host, 0, h - 1)
        used = _per_host_sum(
            jnp.where(running[:, None],
                      jnp.stack([tasks.cores, tasks.gpus], axis=1), 0.0),
            seg, h)
    avail = (hosts.active & hosts.up).astype(jnp.float32)
    return hosts.cores * avail - used[:, 0], hosts.n_gpus * avail - used[:, 1]


def host_utilization(tasks: TaskTable, hosts: HostTable):
    """Per-host CPU/GPU utilization in [0,1] from running tasks."""
    h = hosts.cores.shape[0]
    with telemetry_mod.stage_scope("stage_per_host_sum"):
        running = (tasks.status == RUNNING) & (tasks.host >= 0)
        seg = jnp.clip(tasks.host, 0, h - 1)
        busy = _per_host_sum(
            jnp.where(running[:, None],
                      jnp.stack([tasks.cores * tasks.cpu_util,
                                 tasks.gpus * tasks.gpu_util], axis=1), 0.0),
            seg, h)
    cpu, gpu = busy[:, 0], busy[:, 1]
    cpu_u = jnp.where(hosts.cores > 0, cpu / jnp.maximum(hosts.cores, 1e-6), 0.0)
    gpu_u = jnp.where(hosts.n_gpus > 0, gpu / jnp.maximum(hosts.n_gpus, 1e-6), 0.0)
    return jnp.clip(cpu_u, 0.0, 1.0), jnp.clip(gpu_u, 0.0, 1.0)


def _eligible(tasks: TaskTable, now, shift_ok):
    arrived = tasks.arrival <= now
    return (tasks.status == PENDING) & arrived & shift_ok


def _first_k_indices(mask, k: int):
    """Indices of the first k True rows of mask (padded with -1).

    csum[i] counts True rows in [0..i], so the s-th True index is the first
    i with csum[i] == s + 1 — k binary searches on the sorted cumsum instead
    of the scatter this used to be (XLA CPU scatters serialize; inside the
    per-step hot loop that was most of the scheduler's fixed cost).
    """
    csum = jnp.cumsum(mask.astype(jnp.int32))
    wanted = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(csum, wanted, side="left").astype(jnp.int32)
    return jnp.where(wanted <= csum[-1], idx, -1)


def _first_k_by_priority(mask, priority, k: int, levels: int):
    """First k True rows of mask in (priority desc, arrival) order.

    ONE sorted-key pass: the level-major flattened mask `[L, T] -> [L*T]`
    (levels descending, rows in arrival order within each level) is already
    sorted by the composite key (priority level, arrival), so a single
    cumsum + searchsorted selects the first k set bits and `idx % T`
    recovers the task rows.  The per-level form this replaces
    (`_first_k_by_priority_reference`) ran `levels + 1` cumsum passes and a
    gather merge — per step, inside the hot loop, and batched over every
    grid cell; it was the single largest term in the typed-variant vmap
    collapse.  `priority` may be traced.

    Equivalence with the reference (which truncates each level to k before
    merging): a row dropped by a per-level truncation sits at position
    >= k within its OWN level, so at position >= k of the merged order too
    — never selectable among the first k.  Pinned by differential tests
    (hypothesis + lexsort model) in tests/test_core_properties.py.
    """
    prio = jnp.asarray(priority)
    t = prio.shape[0]
    lvl = jnp.arange(levels - 1, -1, -1, dtype=prio.dtype)
    m = (mask[None, :] & (prio[None, :] == lvl[:, None])).reshape(-1)
    csum = jnp.cumsum(m.astype(jnp.int32))
    wanted = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(csum, wanted, side="left").astype(jnp.int32)
    return jnp.where(wanted <= csum[-1], idx % t, -1)


def _first_k_by_priority_reference(mask, priority, k: int, levels: int):
    """Per-level reference form of `_first_k_by_priority` (kept as the
    differential-test oracle): one `_first_k_indices` pass per priority
    level, then one merge pass over the concatenated per-level candidate
    lists.  Higher classes fill the k slots first; FIFO (row) order is
    preserved within a class because each per-level pass already returns
    rows in arrival order.
    """
    prio = jnp.asarray(priority)
    cands = [_first_k_indices(mask & (prio == p), k)
             for p in range(levels - 1, -1, -1)]
    cat = jnp.concatenate(cands)                  # [levels*k]
    sel = _first_k_indices(cat >= 0, k)           # first k valid candidates
    return jnp.where(sel >= 0, cat[jnp.maximum(sel, 0)], -1)


def schedule_first_fit(tasks: TaskTable, hosts: HostTable, now, shift_ok,
                       cfg: SchedulerConfig, slots=None, host_order=None,
                       presorted: bool = False):
    """Exact bounded first-fit.  Returns `(tasks, iters, placed, binds)`:
    the updated task table, the placement loop's iterations, the slots it
    placed (the loop's work and its useful part) and whether the placement
    bound bound, 1 where more tasks were eligible than it let through
    (f32 scalars).

    `cfg.slots_per_step` is the STATIC placement bound (it shapes the
    compiled loop).  `slots`, when given, is a TRACED per-run slot count
    <= that bound: iterations past it become no-ops, so a scenario grid can
    sweep `dyn_axis(slots_per_step=...)` inside ONE compiled program — the
    fori_loop bound used to be the swept value itself, recompiling per
    point.  `slots=None` reproduces the static path bit-for-bit.

    `host_order` (i32[H] permutation, e.g. resilience.host_rank) makes the
    "first" in first-fit mean "first in that order" — failure-reactive
    placement.  None keeps natural host order.  Either way a down or
    deactivated host never fits, even for zero-footprint tasks: `0 >= 0`
    used to admit a coreless task onto a failed host (whose free capacity
    reads as exactly 0), parking it there forever.

    `presorted=True` asserts the table rows are ALREADY in
    (priority desc, arrival) order (`state.priority_schedule_order`), so
    priority admission is the plain FIFO prefix of the row order and the
    per-step `[L*T]` level-major flatten+cumsum disappears entirely.  The
    engine permutes the table once per simulation and sets this; direct
    callers with arrival-ordered tables keep the default.

    Under a telemetry session the parts are named for device traces:
    `stage_scheduler.candidates` (eligibility, cumsum, searchsorted and the
    per-slot needs), `stage_scheduler.free_capacity` (per-host sums),
    `stage_scheduler.first_fit` (the placement loop) and
    `stage_scheduler.commit` (the placed rows' table writes,
    `commit_placements`).
    """
    k = cfg.slots_per_step
    t = tasks.arrival.shape[0]
    scope = telemetry_mod.stage_scope
    with scope("stage_scheduler.candidates"):
        elig = _eligible(tasks, now, shift_ok)
        multi = cfg.priority_levels > 1 and not presorted
        if multi:
            # level-major flattened mask: merged (priority desc, arrival)
            prio = jnp.asarray(tasks.priority)
            lvl = jnp.arange(cfg.priority_levels - 1, -1, -1,
                             dtype=prio.dtype)
            m = (elig[None, :] & (prio[None, :] == lvl[:, None])).reshape(-1)
        else:  # single class, or presorted rows: row order IS admission order
            m = elig
        # One cumsum maps slot -> row (cand, via k binary searches: the
        # k-th set bit of m sits at the first position whose cumsum equals
        # k+1) and counts the eligible tasks (its last entry).
        csum = jnp.cumsum(m.astype(jnp.int32))
        wanted = jnp.arange(1, k + 1, dtype=jnp.int32)
        idx = jnp.searchsorted(csum, wanted, side="left").astype(jnp.int32)
        cand = jnp.where(wanted <= csum[-1], idx % t if multi else idx, -1)
    with scope("stage_scheduler.free_capacity"):
        free_c, free_g = free_capacity(tasks, hosts)
    usable = hosts.active & hosts.up
    with scope("stage_scheduler.candidates"):
        # per-slot resource needs, gathered ONCE before the loop (the body
        # used to re-gather from the [T] columns every iteration, a batched
        # gather per iteration under vmapped grids)
        cj = jnp.maximum(cand, 0)
        nc_all = jnp.where(cand >= 0, tasks.cores[cj], 0.0)
        ng_all = jnp.where(cand >= 0, tasks.gpus[cj], 0.0)
        # suffix minima of the per-slot needs: the placement loop's early
        # stop (`first_fit_loop`)
        inf32 = jnp.float32(jnp.inf)
        suf_c = jax.lax.cummin(
            jnp.where(cand >= 0, nc_all, inf32)[::-1])[::-1]
        suf_g = jax.lax.cummin(
            jnp.where(cand >= 0, ng_all, inf32)[::-1])[::-1]
        # the valid slots: the -1-padded candidate list is a prefix, and so
        # is the traced slot bound's (iterations past it are no-ops)
        valid = cand >= 0
        if slots is not None:
            valid = valid & (jnp.arange(k) < slots)
        n_valid = jnp.sum(valid.astype(jnp.int32))

    with scope("stage_scheduler.first_fit"):
        if first_fit_form() == "pallas":
            from repro.kernels import ops  # lazy: kernels import core
            sel_host, iters = ops.first_fit(
                nc_all, ng_all, suf_c, suf_g, n_valid, free_c, free_g,
                usable, host_order)
        else:
            telemetry_mod.note_first_fit("while_loop")
            sel_host, iters = first_fit_loop(
                nc_all, ng_all, suf_c, suf_g, n_valid, free_c, free_g,
                usable, host_order)
    with scope("stage_scheduler.commit"):
        tasks = commit_placements(tasks, hosts, now, cand, sel_host)
        n_placed = jnp.sum((sel_host >= 0).astype(jnp.float32))
    # the bound binds where more tasks were eligible than it let through
    bound = k if slots is None else jnp.minimum(slots, k)
    binds = (csum[-1] > bound).astype(jnp.float32)
    return tasks, iters.astype(jnp.float32), n_placed, binds


def commit_placements(tasks: TaskTable, hosts: HostTable, now, cand,
                      sel_host):
    """The deferred table writes of one placement pass: each slot that
    placed (`sel_host[i] >= 0`) starts row `cand[i]` on that host.

    The at most k placed rows are written by their row index, O(k) work:
    a lookup of every row's slot (a `[T]` gather from the k-entry result,
    or XLA's select chain over k - 1 slot masks) read and wrote all T.
    The other slots write past the table and are dropped, so the indices
    are unique; rows map to at most one slot.  `first_start` takes a
    scatter-min, bitwise `minimum(first_start, now)` on the placed rows.
    A placed row's host speed comes from a k-entry gather of the host
    table: progress then reads a column, where a `[T]` gather from the
    host table took XLA's general gather emitter, ~0.7 ms a step at
    93,587 rows on a v5e.
    """
    t = tasks.arrival.shape[0]
    k = cand.shape[0]
    row = jnp.where(sel_host >= 0, cand, t + jnp.arange(k, dtype=cand.dtype))
    at = lambda col: col.at[row]
    opts = dict(mode="drop", unique_indices=True)
    return tasks._replace(
        status=at(tasks.status).set(RUNNING, **opts),
        host=at(tasks.host).set(sel_host.astype(tasks.host.dtype), **opts),
        first_start=at(tasks.first_start).min(now, **opts),
        speed=at(tasks.speed).set(hosts.speed[jnp.maximum(sel_host, 0)],
                                  **opts))


def schedule_aggregate(tasks: TaskTable, hosts: HostTable, now, shift_ok,
                       cfg: SchedulerConfig):
    """Capacity-only admission (fragmentation-blind, analytical-model-like).

    Admits the longest FIFO prefix of eligible tasks whose total core/GPU
    demand fits the total free capacity, then maps each admitted task onto a
    host by position in the free-capacity cumsum (approximate placement).
    """
    elig = _eligible(tasks, now, shift_ok)
    free_c, free_g = free_capacity(tasks, hosts)
    total_c, total_g = jnp.sum(free_c), jnp.sum(free_g)
    need_c = jnp.where(elig, tasks.cores, 0.0)
    need_g = jnp.where(elig, tasks.gpus, 0.0)
    admit = elig & (jnp.cumsum(need_c) <= total_c) & (jnp.cumsum(need_g) <= total_g)
    # approximate host: position of the task's core-demand midpoint in the
    # cumulative free-core distribution over hosts
    cum_c = jnp.cumsum(jnp.maximum(free_c, 0.0))
    pos = jnp.cumsum(need_c) - need_c * 0.5
    host = jnp.searchsorted(cum_c, pos).astype(jnp.int32)
    h = hosts.cores.shape[0]
    host = jnp.clip(host, 0, h - 1)
    # a down/inactive host occupies a zero-width span of the cumsum, yet a
    # zero-need task's midpoint can land exactly on it (0 >= 0); bump every
    # task to the next usable host at-or-after its mapped position, and
    # refuse admission when none exists
    usable = hosts.active & hosts.up
    next_usable = jax.lax.cummin(
        jnp.where(usable, jnp.arange(h, dtype=jnp.int32), h)[::-1])[::-1]
    bumped = next_usable[host]
    admit = admit & (bumped < h)
    host = jnp.where(bumped < h, bumped, 0).astype(jnp.int32)
    return tasks._replace(
        status=jnp.where(admit, RUNNING, tasks.status).astype(jnp.int32),
        host=jnp.where(admit, host, tasks.host).astype(jnp.int32),
        first_start=jnp.where(admit, jnp.minimum(tasks.first_start, now),
                              tasks.first_start),
        speed=jnp.where(admit, hosts.speed[host], tasks.speed),
    )


def schedule_step(tasks: TaskTable, hosts: HostTable, now, shift_ok,
                  cfg: SchedulerConfig, slots=None, host_order=None,
                  presorted: bool = False):
    """One step of the configured scheduler: `(tasks, iters, placed,
    binds)` as `schedule_first_fit` returns them; the aggregate mode runs
    no placement loop and counts 0 for all three."""
    if cfg.mode == "first_fit":
        return schedule_first_fit(tasks, hosts, now, shift_ok, cfg,
                                  slots=slots, host_order=host_order,
                                  presorted=presorted)
    if cfg.mode == "aggregate":
        if cfg.priority_levels > 1:
            raise ValueError(
                "scheduler mode 'aggregate' admits the longest FIFO prefix "
                "and cannot honor priority classes; use mode='first_fit' "
                "with priority_levels > 1")
        zero = jnp.float32(0.0)
        return (schedule_aggregate(tasks, hosts, now, shift_ok, cfg),
                zero, zero, zero)
    raise ValueError(f"unknown scheduler mode '{cfg.mode}'")
