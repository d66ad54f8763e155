"""Closed-loop resilience suite + this PR's bugfix regressions.

Tentpole coverage: the facility failure processes (chiller derate / PDU cap),
the thermal-throttle recurrence, failure-reactive placement (host_rank) and
the fleet-level cross-region spill executor — including the two inertness
guarantees the engine makes: `resilience.enabled=False` leaves the pipeline
untouched (the goldens pin that bit-for-bit), and an ENABLED loop with
`failure_hazard_scale=0.0` reproduces the healthy datacenter to float
tolerance inside the same compiled program.

Satellite bugfix regressions (each fails on the pre-fix code):
  * S1 — zero-footprint tasks (cores=0, gpus=0) were placeable on down or
    inactive hosts: `free >= need` is `0 >= 0` there.  Both schedulers now
    mask with `hosts.active & hosts.up`.
  * S2 — `stage_task_stopper` counted graceful carbon-aware pauses into
    `n_interrupts`, conflating them with failure interruptions.  Pauses now
    land in the additive `n_stops` field.
  * S3 — `forward_window_quantiles` materialized the full [S, W] window
    matrix (~590 MB f32 at a year horizon); it now computes in [chunk, W]
    blocks, bitwise-identical under jit.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CoolingConfig, FailureConfig, FleetSpec,
                        ResilienceConfig,
                        SchedulerConfig, ShiftingConfig, SimConfig,
                        facility_failure_series, host_rank, make_host_table,
                        make_task_table, next_throttle, simulate,
                        simulate_fleet, summarize)
from repro.core import resilience as resilience_mod
from repro.core.scheduler import (schedule_aggregate, schedule_first_fit,
                                  schedule_step)
from repro.core.shifting import forward_window_quantiles
from repro.core.state import (EMPTY_TASK_ROW, INVALID, PENDING, init_metrics,
                              pad_task_table)

S = 96
DT = 0.25


def _tasks(n=24, seed=0, max_arrival=4.0, duration=(0.5, 3.0)):
    rng = np.random.default_rng(seed)
    return make_task_table(np.sort(rng.uniform(0.0, max_arrival, n)),
                           rng.uniform(*duration, n),
                           rng.integers(1, 3, n).astype(float),
                           rng.integers(0, 2, n).astype(float),
                           rng.uniform(0.3, 0.9, n),
                           rng.uniform(0.2, 0.8, n))


def _ci():
    t = np.arange(S) * DT
    return (300 + 150 * np.sin(2 * np.pi * t / 24.0)).astype(np.float32)


HOSTS = make_host_table(4, 4)


# ---------------------------------------------------------------------------
# S1: down/inactive hosts must never receive tasks — not even free ones
# ---------------------------------------------------------------------------

def _zero_footprint_task():
    return make_task_table([0.0], [1.0], [0.0], [0.0], [0.5], [0.0])


@pytest.mark.parametrize("flag", ["up", "active"])
def test_first_fit_skips_unusable_hosts_zero_footprint(flag):
    """cores=0/gpus=0 makes `free >= need` vacuously true on ANY host; the
    down-host mask is the only thing keeping the task off dead hardware."""
    hosts = make_host_table(2, 2)._replace(
        **{flag: jnp.asarray([False, True])})
    out, *_ = schedule_first_fit(_zero_footprint_task(), hosts,
                                   jnp.float32(0.0), jnp.ones(1, bool),
                                   SchedulerConfig())
    assert int(out.host[0]) == 1


@pytest.mark.parametrize("flag", ["up", "active"])
def test_aggregate_skips_unusable_hosts_zero_footprint(flag):
    """The cumsum searchsorted maps a zero-demand task to the FIRST host
    regardless of its state; the next-usable-host bump must redirect it."""
    hosts = make_host_table(2, 2)._replace(
        **{flag: jnp.asarray([False, True])})
    out = schedule_aggregate(_zero_footprint_task(), hosts, jnp.float32(0.0),
                             jnp.ones(1, bool), SchedulerConfig())
    assert int(out.host[0]) == 1


def test_schedulers_leave_task_pending_when_no_host_usable():
    hosts = make_host_table(2, 2)._replace(up=jnp.zeros(2, bool))
    for mode in ("first_fit", "aggregate"):
        out, *_ = schedule_step(_zero_footprint_task(), hosts,
                                  jnp.float32(0.0), jnp.ones(1, bool),
                                  SchedulerConfig(mode=mode))
        assert int(out.status[0]) == PENDING, mode


# ---------------------------------------------------------------------------
# S2: graceful stops are not failure interruptions
# ---------------------------------------------------------------------------

def _stopper_trace():
    """Green for 4 h (tasks start), then red for 10 h (stopper trips): the
    0.35-quantile forward threshold lands on the cheap tail, so the middle
    band reads as high-carbon."""
    ci = np.full(S, 100.0, np.float32)
    ci[16:56] = 800.0
    return ci


def test_stopper_counts_stops_not_interrupts():
    tasks = make_task_table([0.0, 0.5], [12.0, 12.0], [1.0, 1.0])
    cfg = SimConfig(n_steps=S,
                    shifting=ShiftingConfig(enabled=True, stop_running=True,
                                            max_delay_h=24.0))
    final, _ = simulate(tasks, HOSTS, _stopper_trace(), cfg)
    r = summarize(final, cfg)
    assert float(r.n_stops) > 0, "scenario failed to trigger the stopper"
    # failures are disabled: a graceful pause is NOT an interruption
    assert float(r.n_interrupts) == 0.0
    assert float(r.lost_work_h) == 0.0


def test_interrupts_do_not_count_as_stops():
    cfg = SimConfig(n_steps=S,
                    failures=FailureConfig(enabled=True, mtbf_h=2.0,
                                           repair_h=1.0))
    final, _ = simulate(_tasks(), HOSTS, _ci(), cfg)
    r = summarize(final, cfg)
    assert float(r.n_interrupts) > 0, "scenario failed to trigger failures"
    assert float(r.n_stops) == 0.0


# ---------------------------------------------------------------------------
# S3: chunked forward-window quantiles == dense, bitwise under jit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(50, 7), (50, 50), (64, 16), (97, 32)])
def test_chunked_quantiles_bitwise_scalar(s, chunk):
    rng = np.random.default_rng(s + chunk)
    tr = rng.uniform(100, 500, s).astype(np.float32)
    dense = jax.jit(lambda t: forward_window_quantiles(
        t, DT, 6.0, 0.35, chunk_size=10 ** 6))(tr)
    chunked = jax.jit(lambda t: forward_window_quantiles(
        t, DT, 6.0, 0.35, chunk_size=chunk))(tr)
    assert chunked.shape == (s,)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(chunked))


def test_chunked_quantiles_bitwise_stacked_levels():
    rng = np.random.default_rng(3)
    tr = rng.uniform(0.05, 0.4, 50).astype(np.float32)
    q = jnp.asarray([0.2, 0.8])
    dense = jax.jit(lambda t: forward_window_quantiles(
        t, DT, 24.0, q, chunk_size=10 ** 6))(tr)
    chunked = jax.jit(lambda t: forward_window_quantiles(
        t, DT, 24.0, q, chunk_size=7))(tr)
    assert dense.shape == chunked.shape == (2, 50)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(chunked))


# ---------------------------------------------------------------------------
# tentpole: facility failure processes
# ---------------------------------------------------------------------------

RES = ResilienceConfig(enabled=True, chiller_mtbf_h=20.0, chiller_repair_h=2.0,
                       pdu_mtbf_h=30.0, pdu_repair_h=1.0, pdu_cap_kw=2.0)


def test_facility_series_hazard_zero_is_exactly_healthy():
    derate, pdu = facility_failure_series(42, S, DT, RES,
                                          hazard_scale=jnp.float32(0.0))
    assert np.all(np.asarray(derate) == 1.0)
    assert not np.any(np.asarray(pdu))


def test_facility_series_values_and_determinism():
    d1, p1 = facility_failure_series(42, S, DT, RES)
    d2, p2 = facility_failure_series(42, S, DT, RES)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    assert set(np.unique(np.asarray(d1))) <= {np.float32(RES.chiller_derate),
                                              np.float32(1.0)}
    d3, _ = facility_failure_series(43, S, DT, RES)
    assert not np.array_equal(np.asarray(d1), np.asarray(d3))


def _run_lengths(flags):
    runs, n = [], 0
    for f in flags:
        if f:
            n += 1
        elif n:
            runs.append(n)
            n = 0
    return runs, n  # complete runs, trailing (possibly truncated) run


def test_facility_series_repair_lasts_exactly_repair_h():
    cfg = dataclasses.replace(RES, pdu_mtbf_h=8.0, pdu_repair_h=1.5)
    repair_steps = max(int(round(cfg.pdu_repair_h / DT)), 1)
    _, pdu = facility_failure_series(7, 400, DT, cfg)
    runs, tail = _run_lengths(np.asarray(pdu))
    assert runs, "no PDU failure sampled in 400 steps at mtbf=8h"
    assert all(r == repair_steps for r in runs)
    assert tail <= repair_steps


# ---------------------------------------------------------------------------
# tentpole: throttle rule
# ---------------------------------------------------------------------------

def test_next_throttle_thermal_trip():
    cfg = dataclasses.replace(RES, throttle_inlet_c=30.0, throttle_factor=0.5)
    cool = next_throttle(10.0, 10.0, 15.0, 1.0, jnp.inf, cfg)
    hot = next_throttle(10.0, 10.0, 35.0, 1.0, jnp.inf, cfg)
    assert float(cool) == 1.0
    assert float(hot) == 0.5
    # degraded cooling raises the inlet proxy: same load + weather trips
    derated = next_throttle(1000.0, 1000.0, 15.0, 0.5, jnp.inf, cfg)
    assert float(derated) == 0.5
    # the dyn threshold override wins over the static config
    assert float(next_throttle(10.0, 10.0, 35.0, 1.0, jnp.inf, cfg,
                               threshold_c=jnp.float32(99.0))) == 1.0


def test_next_throttle_pdu_headroom():
    cfg = dataclasses.replace(RES, throttle_inlet_c=1e9)
    # demand 40 kW against a 10 kW cap: next step runs at 25%
    t = next_throttle(10.0, 40.0, 15.0, 1.0, jnp.float32(10.0), cfg)
    np.testing.assert_allclose(float(t), 0.25, rtol=1e-6)
    assert float(next_throttle(10.0, 5.0, 15.0, 1.0, jnp.float32(10.0),
                               cfg)) == 1.0


def test_throttling_slows_compute():
    """A permanently tripped throttle must slow actual work, not just
    relabel it: every task finishes no earlier, some strictly later."""
    cfg_off = SimConfig(n_steps=S)
    res = dataclasses.replace(RES, chiller_mtbf_h=1e12, pdu_mtbf_h=1e12,
                              throttle_inlet_c=-100.0, throttle_factor=0.4)
    cfg_on = dataclasses.replace(cfg_off, resilience=res)
    tasks = _tasks()
    s_off, _ = simulate(tasks, HOSTS, _ci(), cfg_off)
    s_on, _ = simulate(tasks, HOSTS, _ci(), cfg_on)
    assert float(summarize(s_on, cfg_on).throttled_h) > 0
    f_off = np.asarray(s_off.tasks.finish)
    f_on = np.asarray(s_on.tasks.finish)
    assert np.all((f_on >= f_off) | ~np.isfinite(f_on))
    done_both = np.isfinite(f_on) & np.isfinite(f_off)
    assert np.any(f_on[done_both] > f_off[done_both])


def test_pdu_cap_clamps_it_power():
    """With the PDU permanently down, total IT draw can never exceed the
    cap, so IT energy is bounded by cap * horizon."""
    cap = 1.5
    res = dataclasses.replace(RES, chiller_mtbf_h=1e12, pdu_mtbf_h=1e-6,
                              pdu_repair_h=1e6, pdu_cap_kw=cap,
                              throttle_inlet_c=1e9)
    cfg = dataclasses.replace(SimConfig(n_steps=S), resilience=res)
    r = summarize(simulate(_tasks(), HOSTS, _ci(), cfg)[0], cfg)
    assert float(r.derate_h) > 0
    assert float(r.it_energy_kwh) <= cap * S * DT * (1 + 1e-5)


# ---------------------------------------------------------------------------
# failure/repair cycle invariants (deterministic single-seed versions of the
# hypothesis tier in tests/test_resilience_properties.py)
# ---------------------------------------------------------------------------

def _failure_run(seed, checkpoint_interval_h, n_steps=24 * 4 * 6):
    rng = np.random.default_rng(seed)
    n = 8
    tasks = make_task_table(np.sort(rng.uniform(0.0, 6.0, n)),
                            rng.uniform(0.25, 3.0, n),
                            rng.integers(1, 3, n).astype(float))
    cfg = SimConfig(n_steps=n_steps, seed=seed,
                    failures=FailureConfig(
                        enabled=True, mtbf_h=5.0, repair_h=1.0,
                        checkpointing=True,
                        checkpoint_interval_h=checkpoint_interval_h))
    ci = (200 + 100 * np.sin(np.arange(n_steps) * DT)).astype(np.float32)
    final, _ = simulate(tasks, make_host_table(3, 4), ci, cfg)
    return final, summarize(final, cfg)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_per_step_checkpointing_loses_no_work(seed):
    """Checkpoint runs before failures within a step, so the boundary
    snapshot at time t covers all work completed by t: with a checkpoint
    every step there is never un-snapshot progress for a failure to
    destroy."""
    _, r_hourly = _failure_run(seed, checkpoint_interval_h=1.0)
    _, r_per_step = _failure_run(seed, checkpoint_interval_h=DT)
    assert float(r_hourly.lost_work_h) >= 0.0
    assert float(r_per_step.lost_work_h) == 0.0
    assert float(r_per_step.n_interrupts) == float(r_hourly.n_interrupts)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interrupted_tasks_eventually_done(seed):
    """Failures requeue work, never drop it: with repairs far shorter than
    the horizon every task still finishes."""
    from repro.core import DONE
    final, r = _failure_run(seed, checkpoint_interval_h=1.0)
    status = np.asarray(final.tasks.status)
    arrival = np.asarray(final.tasks.arrival)
    assert np.all(status[np.isfinite(arrival)] == DONE)


# ---------------------------------------------------------------------------
# tentpole: inertness + dyn-key validation
# ---------------------------------------------------------------------------

def test_disabled_rejects_resilience_dyn_keys():
    for key in ("failure_hazard_scale", "throttle_inlet_c", "pdu_cap_kw"):
        with pytest.raises(ValueError, match=key):
            simulate(_tasks(), HOSTS, _ci(), SimConfig(n_steps=S),
                     dyn={key: jnp.float32(1.0)})


def test_enabled_healthy_matches_disabled():
    """resilience ON with failure_hazard_scale=0.0 (the healthy end of a
    sweep) and benign weather reproduces the disabled engine to float
    tolerance, and its new metrics are exactly zero.  Cooling runs with a
    mild wet-bulb trace: weatherless runs assume setpoint-level wet-bulb
    (the documented worst case), which would trip the thermal throttle."""
    res = dataclasses.replace(RES, chiller_mtbf_h=5.0, pdu_mtbf_h=5.0)
    cool = CoolingConfig(enabled=True)
    cfg_on = dataclasses.replace(SimConfig(n_steps=S, cooling=cool),
                                 resilience=res)
    cfg_off = SimConfig(n_steps=S, cooling=cool)
    tasks = _tasks()
    wb = np.full(S, 15.0, np.float32)
    r_on = summarize(simulate(tasks, HOSTS, _ci(), cfg_on,
                              dyn={"failure_hazard_scale": jnp.float32(0.0)},
                              weather_trace=wb)[0], cfg_on)
    r_off = summarize(simulate(tasks, HOSTS, _ci(), cfg_off,
                               weather_trace=wb)[0], cfg_off)
    for k in ("throttled_h", "derate_h", "n_spills"):
        assert float(getattr(r_on, k)) == 0.0, k
    for k in r_off._fields:
        if getattr(r_off, k) is None:
            continue
        np.testing.assert_allclose(np.asarray(getattr(r_on, k)),
                                   np.asarray(getattr(r_off, k)),
                                   rtol=1e-6, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# tentpole: failure-reactive placement
# ---------------------------------------------------------------------------

def test_host_rank_is_identity_without_failure_history():
    order = host_rank(make_host_table(5, 4), jnp.float32(3.0))
    np.testing.assert_array_equal(np.asarray(order), np.arange(5))


def test_host_rank_sinks_down_and_recently_repaired_hosts():
    hosts = make_host_table(4, 4)._replace(
        up=jnp.asarray([True, False, True, True]),
        repair_at=jnp.asarray([0.0, 9.0, 8.0, 0.0]))
    order = np.asarray(host_rank(hosts, jnp.float32(10.0)))
    # never-failed hosts first (stable: 0 before 3), the host repaired 2 h
    # ago next, the down host last
    np.testing.assert_array_equal(order, [0, 3, 2, 1])


def _stack(*pytrees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *pytrees)


def test_cross_region_spill_moves_interrupted_task():
    w = 3
    # region 0: an interrupted task (PENDING but already started) + hosts down
    t0 = pad_task_table(make_task_table([0.0], [2.0], [1.0]), w)
    t0 = t0._replace(first_start=t0.first_start.at[0].set(0.5))
    t1 = pad_task_table(make_task_table([0.25], [1.0], [1.0]), w)
    tasks = _stack(t0, t1)
    h0 = make_host_table(2, 4)._replace(up=jnp.zeros(2, bool))
    hosts = _stack(h0, make_host_table(2, 4))
    metrics = _stack(init_metrics(), init_metrics())

    out, m = resilience_mod.cross_region_spill(tasks, hosts, metrics, 2)
    st = np.asarray(out.status)
    assert st[0, 0] == INVALID, "source row was not vacated"
    assert st[1, 1] == PENDING, "task did not land in the target's free slot"
    np.testing.assert_allclose(float(out.arrival[1, 1]), 0.0)
    np.testing.assert_allclose(float(out.duration[1, 1]), 2.0)
    np.testing.assert_allclose(np.asarray(m.n_spills), [1.0, 0.0])
    # conservation: one real task left region 0, one arrived in region 1
    assert int(np.isfinite(np.asarray(out.arrival)).sum()) == 2


def test_padded_and_vacated_rows_are_the_empty_row():
    """A padding row and the row a spill vacates hold `EMPTY_TASK_ROW`,
    column by column; the moved row keeps every value it had."""
    w = 3
    t0 = pad_task_table(make_task_table([0.0], [2.0], [1.0], gpus=[1.0],
                                        job_class=[2], sla_grace=[1.0]), w)
    t0 = t0._replace(first_start=t0.first_start.at[0].set(0.5),
                     remaining=t0.remaining.at[0].set(1.5),
                     lost_work=t0.lost_work.at[0].set(0.25),
                     speed=t0.speed.at[0].set(0.5))
    tasks = _stack(t0, pad_task_table(make_task_table([0.25], [1.0], [1.0]),
                                      w))
    hosts = _stack(make_host_table(2, 4)._replace(up=jnp.zeros(2, bool)),
                   make_host_table(2, 4))
    metrics = _stack(init_metrics(), init_metrics())
    out, m = resilience_mod.cross_region_spill(tasks, hosts, metrics, 1)
    assert float(jnp.sum(m.n_spills)) == 1.0
    for f in tasks._fields:
        col, moved = np.asarray(getattr(tasks, f)), np.asarray(getattr(out, f))
        want = np.asarray(getattr(EMPTY_TASK_ROW, f), col.dtype)
        for r, c in ((0, 1), (0, 2), (1, 1), (1, 2)):
            assert col[r, c] == want, f"padded row ({r}, {c}): {f}"
        assert moved[1, 1] == col[0, 0], f"moved row: {f}"
        assert moved[0, 0] == want, f"vacated row: {f}"


def test_cross_region_spill_noop_when_healthy():
    w = 3
    t0 = pad_task_table(make_task_table([0.0], [2.0], [1.0]), w)
    t0 = t0._replace(first_start=t0.first_start.at[0].set(0.5))
    tasks = _stack(t0, pad_task_table(make_task_table([0.25], [1.0], [1.0]), w))
    hosts = _stack(make_host_table(2, 4), make_host_table(2, 4))
    metrics = _stack(init_metrics(), init_metrics())
    out, m = resilience_mod.cross_region_spill(tasks, hosts, metrics, 4)
    for f in tasks._fields:
        np.testing.assert_array_equal(np.asarray(getattr(out, f)),
                                      np.asarray(getattr(tasks, f)), f)
    assert float(jnp.sum(m.n_spills)) == 0.0


# ---------------------------------------------------------------------------
# tentpole: fleet-level spill executor
# ---------------------------------------------------------------------------

def _fleet(r=3):
    t = np.arange(S) * DT
    ci = (300 + 150 * np.sin(2 * np.pi * t / 24.0)).astype(np.float32)
    return FleetSpec(ci_traces=np.tile(ci, (r, 1)))


def test_fleet_spill_differential_no_failures():
    """With failures off the spill hook is a value-preserving no-op, so the
    coupled scan-of-vmap executor must reproduce the plain vmap-of-scan
    fleet cell."""
    tasks = _tasks()
    res_spill = dataclasses.replace(RES, spill_interrupted=True,
                                    chiller_mtbf_h=1e12, pdu_mtbf_h=1e12)
    res_plain = dataclasses.replace(res_spill, spill_interrupted=False)
    cfg_s = dataclasses.replace(SimConfig(n_steps=S), resilience=res_spill)
    cfg_p = dataclasses.replace(SimConfig(n_steps=S), resilience=res_plain)
    out_s = simulate_fleet(tasks, HOSTS, cfg_s, _fleet())
    out_p = simulate_fleet(tasks, HOSTS, cfg_p, _fleet(), width=tasks.n)
    assert float(out_s.total.n_spills) == 0.0
    for k in out_p.total._fields:
        if getattr(out_p.total, k) is None:
            continue
        np.testing.assert_allclose(np.asarray(getattr(out_s.total, k)),
                                   np.asarray(getattr(out_p.total, k)),
                                   rtol=1e-6, atol=1e-6, err_msg=k)


def test_fleet_spill_rescues_tasks_under_failures():
    """Correlated host failures strand interrupted work in the failing
    region; spilling to the healthiest region must recover completions."""
    tasks = _tasks()
    fail = FailureConfig(enabled=True, mtbf_h=6.0, repair_h=1e6)
    res = dataclasses.replace(RES, spill_interrupted=True,
                              chiller_mtbf_h=1e12, pdu_mtbf_h=1e12)
    cfg_s = dataclasses.replace(SimConfig(n_steps=S), failures=fail,
                                resilience=res)
    cfg_p = dataclasses.replace(
        cfg_s, resilience=dataclasses.replace(res, spill_interrupted=False))
    dyn = {"seed": np.asarray([1, 2, 3])}
    out_s = simulate_fleet(tasks, HOSTS, cfg_s, _fleet(), dyn=dyn)
    out_p = simulate_fleet(tasks, HOSTS, cfg_p, _fleet(), dyn=dyn,
                           width=tasks.n)
    assert float(out_s.total.n_spills) > 0
    assert float(out_s.total.n_done) > float(out_p.total.n_done)


def test_fleet_spill_rejects_priority_levels():
    """Spill moves a row into the target region's first INVALID slot, which
    breaks the (priority, arrival) row order the engine presorts each table
    into under first fit with priority levels, so the pair is refused."""
    rng = np.random.default_rng(0)
    n = 48
    tasks = make_task_table(np.sort(rng.uniform(0.0, 4.0, n)),
                            rng.uniform(0.5, 3.0, n),
                            rng.integers(1, 3, n).astype(float),
                            job_class=rng.integers(0, 3, n))
    res = dataclasses.replace(RES, spill_interrupted=True,
                              chiller_mtbf_h=1e12, pdu_mtbf_h=1e12)
    cfg = SimConfig(n_steps=S, scheduler=SchedulerConfig(priority_levels=3),
                    resilience=res)
    with pytest.raises(ValueError, match="priority levels"):
        simulate_fleet(tasks, HOSTS, cfg, _fleet(3))
    # the same fleet without spill runs
    simulate_fleet(tasks, HOSTS, dataclasses.replace(
        cfg, resilience=dataclasses.replace(res, spill_interrupted=False)),
        _fleet(3))


def test_fleet_spill_validation():
    tasks, fleet = _tasks(), _fleet()
    res = dataclasses.replace(ResilienceConfig(), spill_interrupted=True)
    with pytest.raises(ValueError, match="resilience.enabled"):
        simulate_fleet(tasks, HOSTS,
                       dataclasses.replace(SimConfig(n_steps=S),
                                           resilience=res), fleet)
    res_on = dataclasses.replace(res, enabled=True)
    with pytest.raises(ValueError, match="stage-pipeline"):
        simulate_fleet(tasks, HOSTS,
                       dataclasses.replace(SimConfig(n_steps=S,
                                                     backend="megakernel"),
                                           resilience=res_on), fleet)
