"""Behavioural tests for the tensorized STEAM engine (paper semantics)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatteryConfig, FailureConfig, PENDING, RUNNING, DONE,
                        SchedulerConfig, ShiftingConfig, SimConfig, simulate,
                        summarize, make_host_table, make_task_table, with_scale,
                        carbon_reduction_pct)
from repro.core.analytical import analytical_shifting_savings


def flat_trace(n, value=100.0):
    return jnp.full((n,), value, jnp.float32)


def square_trace(n, high=400.0, low=50.0, period=96, duty=0.5):
    t = np.arange(n)
    return jnp.asarray(np.where((t % period) < duty * period, high, low),
                       jnp.float32)


def tiny_workload(n_tasks=16, arrival_spread=4.0, dur=1.0, cores=2, seed=0):
    rng = np.random.default_rng(seed)
    arrival = np.sort(rng.uniform(0.0, arrival_spread, n_tasks))
    return make_task_table(arrival, np.full(n_tasks, dur),
                           np.full(n_tasks, cores))


@functools.cache
def _compiled(cfg):
    """Module-wide jit cache: tasks/hosts/trace are traced ARGUMENTS (not
    closed-over constants), so tests that share a config and table shapes —
    including with_scale'd host variants — share one compilation instead of
    building a fresh jit wrapper per call."""
    return jax.jit(lambda tasks, hosts, tr: simulate(tasks, hosts, tr, cfg))


def run(tasks, hosts, trace, cfg):
    final, series = _compiled(cfg)(tasks, hosts, trace)
    return summarize(final, cfg), final, series


class TestBasicExecution:
    def test_all_tasks_complete(self):
        tasks = tiny_workload()
        hosts = make_host_table(4, 8)
        cfg = SimConfig(n_steps=200)
        res, final, _ = run(tasks, hosts, flat_trace(200), cfg)
        assert float(res.done_frac) == 1.0
        assert float(res.sla_violation_frac) == 0.0
        assert np.all(np.asarray(final.tasks.status) == DONE)

    def test_finish_times_consistent(self):
        tasks = tiny_workload(n_tasks=4, arrival_spread=0.0, dur=2.0, cores=1)
        hosts = make_host_table(4, 4)
        cfg = SimConfig(n_steps=100)
        res, final, _ = run(tasks, hosts, flat_trace(100), cfg)
        finish = np.asarray(final.tasks.finish)
        # all four run immediately: finish ~ first step + duration
        np.testing.assert_allclose(finish, 2.0 + cfg.dt_h * 0, atol=cfg.dt_h)

    def test_fifo_order_single_slot(self):
        # one host, one core; 1-core tasks must finish in arrival order
        arrival = np.array([0.0, 0.3, 0.6, 0.9])
        tasks = make_task_table(arrival, np.full(4, 1.0), np.ones(4))
        hosts = make_host_table(1, 1)
        cfg = SimConfig(n_steps=100)
        _, final, _ = run(tasks, hosts, flat_trace(100), cfg)
        finish = np.asarray(final.tasks.finish)
        assert np.all(np.diff(finish) > 0)

    def test_capacity_never_exceeded(self):
        tasks = tiny_workload(n_tasks=64, arrival_spread=2.0, cores=4, seed=1)
        hosts = make_host_table(3, 8)
        cfg = SimConfig(n_steps=400, collect_series=True)
        _, final, series = run(tasks, hosts, flat_trace(400), cfg)
        assert float(jnp.max(series["max_overcommit"])) <= 1e-5

    def test_energy_and_carbon_nonnegative_and_consistent(self):
        tasks = tiny_workload()
        hosts = make_host_table(4, 8)
        cfg = SimConfig(n_steps=200)
        res, _, _ = run(tasks, hosts, flat_trace(200, 250.0), cfg)
        assert float(res.grid_energy_kwh) > 0
        # flat trace: op carbon = energy * ci / 1000 exactly
        np.testing.assert_allclose(float(res.op_carbon_kg),
                                   float(res.grid_energy_kwh) * 250.0 / 1000.0,
                                   rtol=1e-5)
        assert float(res.peak_power_kw) * cfg.n_steps * cfg.dt_h >= float(
            res.grid_energy_kwh)

    def test_determinism(self):
        tasks = tiny_workload(seed=3)
        hosts = make_host_table(2, 8)
        cfg = SimConfig(n_steps=300,
                        failures=FailureConfig(enabled=True, mtbf_h=20.0))
        r1, _, _ = run(tasks, hosts, flat_trace(300), cfg)
        r2, _, _ = run(tasks, hosts, flat_trace(300), cfg)
        assert float(r1.total_carbon_kg) == float(r2.total_carbon_kg)
        assert float(r1.n_interrupts) == float(r2.n_interrupts)

    def test_dt_convergence(self):
        tasks = tiny_workload(n_tasks=32, arrival_spread=10.0, seed=5)
        hosts = make_host_table(4, 8)
        res = {}
        for dt in (0.5, 0.25):
            n = int(100 / dt)
            cfg = SimConfig(n_steps=n, dt_h=dt)
            res[dt], _, _ = run(tasks, hosts,
                                square_trace(n, period=int(24 / dt)), cfg)
        a, b = (float(res[dt].total_carbon_kg) for dt in (0.5, 0.25))
        assert abs(a - b) / b < 0.03


class TestScheduler:
    def test_first_fit_packs_first_host(self):
        # 2 hosts x 4 cores; two 2-core tasks at t=0 -> both on host 0
        tasks = make_task_table(np.zeros(2), np.full(2, 5.0), np.full(2, 2.0))
        hosts = make_host_table(2, 4)
        cfg = SimConfig(n_steps=4)
        _, final, _ = run(tasks, hosts, flat_trace(4), cfg)
        assert np.all(np.asarray(final.tasks.host) == 0)

    def test_big_task_skipped_small_task_placed(self):
        # host with 4 cores; 8-core task cannot ever run, 2-core task can
        tasks = make_task_table(np.zeros(2), np.ones(2),
                                np.array([8.0, 2.0]))
        hosts = make_host_table(1, 4)
        cfg = SimConfig(n_steps=50)
        _, final, _ = run(tasks, hosts, flat_trace(50), cfg)
        status = np.asarray(final.tasks.status)
        # arrival sort keeps order; task 0 is the 8-core one
        cores = np.asarray(final.tasks.cores)
        big, small = int(np.argmax(cores)), int(np.argmin(cores))
        assert status[big] == PENDING and status[small] == DONE

    def test_aggregate_mode_admits_fragmented(self):
        # two hosts 3/4-occupied cannot first-fit a 2-core task, but the
        # capacity-only model admits it (the paper's §III critique)
        arrival = np.array([0.0, 0.0, 0.5])
        dur = np.array([10.0, 10.0, 1.0])
        cores = np.array([3.0, 3.0, 2.0])     # fillers fragment both hosts
        tasks = make_task_table(arrival, dur, cores)
        hosts = make_host_table(2, 4)
        for mode, expect_done in [("first_fit", False), ("aggregate", True)]:
            cfg = SimConfig(n_steps=32,
                            scheduler=SchedulerConfig(mode=mode))
            _, final, _ = run(tasks, hosts, flat_trace(32), cfg)
            idx = int(np.argmin(np.asarray(final.tasks.cores)))
            assert (np.asarray(final.tasks.status)[idx] == DONE) == expect_done, mode

    def test_slots_per_step_bounds_placements(self):
        tasks = tiny_workload(n_tasks=32, arrival_spread=0.0, dur=10.0, cores=1)
        hosts = make_host_table(8, 8)
        cfg = SimConfig(n_steps=2, collect_series=True,
                        scheduler=SchedulerConfig(slots_per_step=4))
        _, final, series = run(tasks, hosts, flat_trace(2), cfg)
        assert int(series["n_running"][0]) == 4
        assert int(series["n_running"][1]) == 8


class TestShifting:
    def test_tasks_wait_for_green_period(self):
        # red for 12h then green; tasks at t=0 should start at ~12h
        n = 400
        t = np.arange(n) * 0.25
        trace = jnp.asarray(np.where(t < 12.0, 500.0, 50.0), jnp.float32)
        tasks = make_task_table(np.zeros(4), np.ones(4), np.ones(4))
        hosts = make_host_table(4, 4)
        cfg = SimConfig(n_steps=n, shifting=ShiftingConfig(enabled=True))
        res, final, _ = run(tasks, hosts, trace, cfg)
        fs = np.asarray(final.tasks.first_start)
        assert np.all(fs >= 11.5) and np.all(fs <= 13.0)

    def test_max_delay_fallback(self):
        # permanently red: tasks must start anyway after 24h
        tasks = make_task_table(np.zeros(4), np.ones(4), np.ones(4))
        hosts = make_host_table(4, 4)
        n = 200
        trace = jnp.concatenate([jnp.full((n // 2,), 500.0),
                                 jnp.full((n // 2,), 499.0)]).astype(jnp.float32)
        cfg = SimConfig(n_steps=n, shifting=ShiftingConfig(enabled=True))
        res, final, _ = run(tasks, hosts, trace, cfg)
        fs = np.asarray(final.tasks.first_start)
        assert np.all(fs >= 23.5) and np.all(fs <= 25.0)

    def test_shifting_reduces_op_carbon_diurnal(self):
        n = 24 * 4 * 14
        trace = square_trace(n, high=600.0, low=30.0, period=96)
        rng = np.random.default_rng(7)
        arrival = np.sort(rng.uniform(0, 24 * 10, 64))
        tasks = make_task_table(arrival, np.full(64, 2.0), np.full(64, 2.0))
        hosts = make_host_table(8, 8)
        base, _, _ = run(tasks, hosts, trace, SimConfig(n_steps=n))
        shift, _, _ = run(tasks, hosts, trace,
                          SimConfig(n_steps=n,
                                    shifting=ShiftingConfig(enabled=True)))
        assert float(shift.op_carbon_kg) < float(base.op_carbon_kg)
        assert float(shift.mean_start_delay_h) > float(base.mean_start_delay_h)

    def test_analytical_exceeds_simulated_savings(self):
        # the paper's §III point: capacity-blind oracle >= full simulation
        n = 24 * 4 * 7
        trace = square_trace(n, high=600.0, low=30.0, period=96)
        rng = np.random.default_rng(11)
        arrival = np.sort(rng.uniform(0, 24 * 5, 96))
        dur = np.full(96, 2.0)
        tasks = make_task_table(arrival, dur, np.full(96, 4.0))
        hosts = make_host_table(2, 8)   # tight capacity -> stacking
        base, _, _ = run(tasks, hosts, trace, SimConfig(n_steps=n))
        shift, _, _ = run(tasks, hosts, trace,
                          SimConfig(n_steps=n,
                                    shifting=ShiftingConfig(enabled=True)))
        sim_savings = 100.0 * (1 - float(shift.op_carbon_kg)
                               / float(base.op_carbon_kg))
        ana_savings, _ = analytical_shifting_savings(arrival, dur,
                                                     np.asarray(trace), 0.25)
        assert float(ana_savings) > sim_savings


class TestBattery:
    def test_charge_bounded_and_discharges(self):
        n = 24 * 4 * 7
        trace = square_trace(n, high=500.0, low=50.0, period=96)
        tasks = tiny_workload(n_tasks=32, arrival_spread=100.0, dur=4.0, seed=2)
        hosts = make_host_table(4, 8)
        cfg = SimConfig(n_steps=n, collect_series=True,
                        battery=BatteryConfig(enabled=True, capacity_kwh=5.0))
        res, final, series = run(tasks, hosts, trace, cfg)
        charge = np.asarray(series["battery_charge"])
        assert np.all(charge >= -1e-5) and np.all(charge <= 5.0 + 1e-5)
        assert float(res.batt_discharged_kwh) > 0

    def test_battery_raises_peak_power(self):
        n = 24 * 4 * 7
        trace = square_trace(n, high=500.0, low=50.0, period=96)
        tasks = tiny_workload(n_tasks=32, arrival_spread=100.0, dur=4.0, seed=2)
        hosts = make_host_table(4, 8)
        base, _, _ = run(tasks, hosts, trace, SimConfig(n_steps=n))
        batt, _, _ = run(tasks, hosts, trace, SimConfig(
            n_steps=n, battery=BatteryConfig(enabled=True, capacity_kwh=20.0)))
        assert float(batt.peak_power_kw) > 2.0 * float(base.peak_power_kw)

    def test_battery_helps_high_variance_region(self):
        n = 24 * 4 * 14
        trace = square_trace(n, high=800.0, low=20.0, period=96)
        tasks = tiny_workload(n_tasks=64, arrival_spread=200.0, dur=6.0,
                              cores=4, seed=4)
        hosts = make_host_table(4, 8)
        base, _, _ = run(tasks, hosts, trace, SimConfig(n_steps=n))
        batt, _, _ = run(tasks, hosts, trace, SimConfig(
            n_steps=n, battery=BatteryConfig(enabled=True, capacity_kwh=10.0)))
        assert float(batt.op_carbon_kg) < float(base.op_carbon_kg)

    def test_battery_hurts_flat_region(self):
        # no variation -> battery only adds embodied carbon (paper F3)
        n = 24 * 4 * 7
        tasks = tiny_workload(n_tasks=16, arrival_spread=50.0)
        hosts = make_host_table(2, 8)
        base, _, _ = run(tasks, hosts, flat_trace(n, 300.0), SimConfig(n_steps=n))
        batt, _, _ = run(tasks, hosts, flat_trace(n, 300.0), SimConfig(
            n_steps=n, battery=BatteryConfig(enabled=True, capacity_kwh=50.0)))
        assert float(batt.total_carbon_kg) > float(base.total_carbon_kg)


class TestFailures:
    def test_failures_interrupt_and_lose_work(self):
        tasks = tiny_workload(n_tasks=32, arrival_spread=2.0, dur=20.0, cores=4,
                              seed=6)
        hosts = make_host_table(4, 8)
        n = 24 * 4 * 7
        cfg = SimConfig(n_steps=n, failures=FailureConfig(
            enabled=True, mtbf_h=30.0, repair_h=2.0))
        res, final, _ = run(tasks, hosts, flat_trace(n), cfg)
        assert float(res.n_interrupts) > 0
        assert float(res.lost_work_h) > 0

    def test_checkpointing_reduces_lost_work(self):
        tasks = tiny_workload(n_tasks=32, arrival_spread=2.0, dur=20.0, cores=4,
                              seed=6)
        hosts = make_host_table(4, 8)
        n = 24 * 4 * 7
        base = FailureConfig(enabled=True, mtbf_h=30.0, repair_h=2.0)
        with_ck, _, _ = run(tasks, hosts, flat_trace(n),
                            SimConfig(n_steps=n, failures=base))
        no_ck, _, _ = run(tasks, hosts, flat_trace(n), SimConfig(
            n_steps=n, failures=FailureConfig(enabled=True, mtbf_h=30.0,
                                              repair_h=2.0,
                                              checkpointing=False)))
        assert float(with_ck.lost_work_h) < float(no_ck.lost_work_h)

    def test_failures_hurt_sla_when_tight(self):
        tasks = tiny_workload(n_tasks=48, arrival_spread=24.0, dur=8.0, cores=8,
                              seed=8)
        hosts = make_host_table(3, 8)
        n = 24 * 4 * 10
        ok, _, _ = run(tasks, hosts, flat_trace(n), SimConfig(n_steps=n))
        bad, _, _ = run(tasks, hosts, flat_trace(n), SimConfig(
            n_steps=n, failures=FailureConfig(enabled=True, mtbf_h=10.0,
                                              repair_h=8.0)))
        assert float(bad.sla_violation_frac) >= float(ok.sla_violation_frac)


class TestHorizontalScaling:
    def test_fewer_hosts_less_carbon_until_sla_breaks(self):
        rng = np.random.default_rng(9)
        arrival = np.sort(rng.uniform(0, 24 * 5, 128))
        tasks = make_task_table(arrival, np.full(128, 3.0), np.full(128, 4.0))
        hosts = make_host_table(8, 8)
        n = 24 * 4 * 7
        cfg = SimConfig(n_steps=n)
        full, _, _ = run(tasks, hosts, flat_trace(n), cfg)
        half, _, _ = run(tasks, with_scale(hosts, 4), flat_trace(n), cfg)
        one, _, _ = run(tasks, with_scale(hosts, 1), flat_trace(n), cfg)
        assert float(half.total_carbon_kg) < float(full.total_carbon_kg)
        assert float(one.sla_violation_frac) > float(half.sla_violation_frac)


def test_sustainability_extras():
    """§XI extensions: water/cost are consistent linear images of energy."""
    import numpy as np
    from repro.core.metrics import sustainability_extras
    from repro.core import SimConfig, simulate, summarize, make_task_table, \
        make_host_table
    tasks = make_task_table([0.0, 1.0], [4.0, 2.0], [4.0, 2.0])
    hosts = make_host_table(2, 8.0)
    cfg = SimConfig(dt_h=0.25, n_steps=96)
    ci = np.full(96, 300.0, np.float32)
    res = summarize(simulate(tasks, hosts, ci, cfg)[0], cfg)
    ex = sustainability_extras(res)
    assert float(ex.water_l) > 0
    assert abs(float(ex.energy_cost) - 0.12 * float(res.grid_energy_kwh)) < 1e-4
    # doubling tariff doubles cost, water unchanged
    ex2 = sustainability_extras(res, price_per_kwh=0.24)
    assert abs(float(ex2.energy_cost) - 2 * float(ex.energy_cost)) < 1e-4
    assert float(ex2.water_l) == float(ex.water_l)


def test_spatial_assignment_properties():
    """Spatial shifting: every valid task is placed; caps are respected;
    carbon-aware placement prefers greener regions."""
    import numpy as np
    from repro.core import make_task_table
    from repro.core.spatial import spatial_assign, split_by_region
    rng = np.random.default_rng(0)
    n = 64
    tasks = make_task_table(np.sort(rng.uniform(0, 24, n)),
                            rng.uniform(0.5, 4.0, n),
                            rng.integers(1, 4, n).astype(float))
    s = 2 * 96
    t = np.arange(s) * 0.25
    traces = np.stack([np.full(s, 100.0),            # green region
                       np.full(s, 500.0),            # dirty region
                       400 + 300 * np.sin(2 * np.pi * t / 24)])  # variable
    region = spatial_assign(tasks, traces, 0.25)
    valid = np.isfinite(np.asarray(tasks.arrival))
    assert np.all(np.asarray(region)[valid] >= 0)
    counts = np.bincount(np.asarray(region)[valid], minlength=3)
    assert counts[0] > counts[1]      # green region preferred over dirty
    # capacity cap binds
    work = np.asarray(tasks.cores) * np.asarray(tasks.duration)
    cap = np.full(3, float(np.sum(work[valid])) / 3)
    region_c = spatial_assign(tasks, traces, 0.25, capacity_core_h=cap)
    loads = np.zeros(3)
    for i in np.where(valid)[0]:
        loads[region_c[i]] += work[i]
    assert np.all(loads <= cap * 1.5 + max(work))  # fallback slack only
    split = split_by_region(tasks, region_c, 3)
    assert split.arrival.shape[0] == 3


def test_straggler_hosts_slow_tasks_and_hurt_sla():
    """Straggler modeling: slow hosts inflate completion times; a scaled-up
    fleet absorbs the effect (the HS x straggler interaction)."""
    import numpy as np
    from repro.core import SimConfig, simulate, summarize, make_task_table, \
        make_host_table
    n = 24
    rng = np.random.default_rng(3)
    tasks = make_task_table(np.sort(rng.uniform(0, 12, n)),
                            np.full(n, 4.0), np.full(n, 4.0))
    ci = np.full(24 * 8, 300.0, np.float32)
    cfg = SimConfig(dt_h=0.25, n_steps=24 * 8, sla_grace_h=2.0)

    fast = make_host_table(4, 8.0)
    slow = make_host_table(4, 8.0, straggler_frac=0.99, straggler_speed=0.4)
    res_f, _, _ = run(tasks, fast, jnp.asarray(ci), cfg)
    res_s, _, _ = run(tasks, slow, jnp.asarray(ci), cfg)
    # stragglers strictly inflate mean completion delay
    assert float(res_s.mean_delay_h) > float(res_f.mean_delay_h) + 1.0
    assert float(res_s.sla_violation_frac) >= float(res_f.sla_violation_frac)
    # over-provisioning mitigates: more (slow) hosts reduce queueing delay
    slow_big = make_host_table(12, 8.0, straggler_frac=0.99,
                               straggler_speed=0.4)
    res_b, _, _ = run(tasks, slow_big, jnp.asarray(ci), cfg)
    assert float(res_b.mean_delay_h) <= float(res_s.mean_delay_h) + 1e-6


def _host_table_progress(cfg):
    """The progress stage as it was before placement wrote each task's
    host speed to the task table: every step it gathered every row's speed
    from the host table.  The oracle for the speed column."""
    resil = cfg.resilience.enabled

    def fn(state, ctx):
        tasks = state.tasks
        running = tasks.status == RUNNING
        h = state.hosts.speed.shape[0]
        speed = state.hosts.speed[jnp.clip(tasks.host, 0, h - 1)]
        if resil:
            speed = speed * state.throttle
        advance = cfg.dt_h * jnp.where(running, speed, 1.0)
        done_now = running & (tasks.remaining <= advance)
        finish = jnp.where(done_now,
                           state.t + tasks.remaining / jnp.maximum(speed, 1e-6),
                           tasks.finish)
        remaining = jnp.where(running,
                              jnp.maximum(tasks.remaining - advance, 0.0),
                              tasks.remaining)
        tasks = tasks._replace(
            remaining=remaining, finish=finish,
            status=jnp.where(done_now, DONE, tasks.status).astype(jnp.int32),
            host=jnp.where(done_now, -1, tasks.host).astype(jnp.int32))
        return state._replace(tasks=tasks), ctx
    return fn


@pytest.mark.parametrize("backend,mode", [("stage-pipeline", "first_fit"),
                                          ("megakernel", "first_fit"),
                                          ("stage-pipeline", "aggregate")])
def test_placed_speed_column_is_the_host_speed(backend, mode, monkeypatch):
    """Placement copies the host's speed into the task row: after every
    step each RUNNING row's `speed` is bitwise `hosts.speed[host]`, under
    straggler hosts, failures (requeue) and the task stopper (pause), and
    a run's finish times and remaining work are bitwise those of the old
    per-step host-table gather, in both scheduler modes."""
    from repro.core import engine
    n_tasks, n = 48, 24 * 4 * 4
    rng = np.random.default_rng(11)
    tasks = make_task_table(np.sort(rng.uniform(0.0, 48.0, n_tasks)),
                            rng.uniform(1.0, 10.0, n_tasks),
                            rng.integers(1, 5, n_tasks).astype(float))
    hosts = make_host_table(6, 8.0, straggler_frac=0.5, straggler_speed=0.4,
                            seed=1)
    host_speed = np.asarray(hosts.speed)
    assert np.any(host_speed == np.float32(0.4)) and np.any(host_speed == 1.0)
    cfg = SimConfig(n_steps=n, backend=backend,
                    scheduler=SchedulerConfig(mode=mode),
                    failures=FailureConfig(enabled=True, mtbf_h=20.0,
                                           repair_h=2.0),
                    shifting=ShiftingConfig(enabled=True, stop_running=True,
                                            forecast_window_h=24.0,
                                            max_delay_h=12.0))
    trace = square_trace(n, period=48)

    mismatches = []
    new_progress = engine.stage_progress

    def checked_progress(cfg_):
        step = new_progress(cfg_)

        def fn(state, ctx):
            state, ctx = step(state, ctx)
            t = state.tasks
            h = state.hosts.speed.shape[0]
            bad = (t.status == RUNNING) & (
                t.speed != state.hosts.speed[jnp.clip(t.host, 0, h - 1)])
            jax.debug.callback(lambda b: mismatches.append(int(b)),
                               jnp.sum(bad.astype(jnp.int32)))
            return state, ctx
        return fn

    monkeypatch.setattr(engine, "stage_progress", checked_progress)
    final, _ = simulate(tasks, hosts, trace, cfg)
    jax.block_until_ready(final)
    assert len(mismatches) == n and sum(mismatches) == 0
    assert float(final.metrics.n_interrupts) > 0
    assert float(final.metrics.n_stops) > 0
    assert np.any(np.asarray(final.tasks.speed) == np.float32(0.4))

    monkeypatch.setattr(engine, "stage_progress", _host_table_progress)
    oracle, _ = simulate(tasks, hosts, trace, cfg)
    for col in ("finish", "remaining", "status", "first_start"):
        np.testing.assert_array_equal(np.asarray(getattr(final.tasks, col)),
                                      np.asarray(getattr(oracle.tasks, col)),
                                      err_msg=col)
    assert np.isfinite(np.asarray(final.tasks.finish)).sum() > n_tasks // 2


def _commit_by_rank(tasks, hosts, now, elig, sel_host, k):
    """The commit as a `[T]` lookup of each row's slot (its rank among the
    eligible rows) in the k-entry placement result: the oracle of
    `scheduler.commit_placements`, whose work is O(k)."""
    rank = jnp.cumsum(elig.astype(jnp.int32)) - 1
    slot_t = jnp.clip(rank, 0, k - 1)
    host_t = sel_host[slot_t]
    sel_speed = hosts.speed[jnp.maximum(sel_host, 0)]
    placed_t = elig & (rank < k) & (host_t >= 0)
    return tasks._replace(
        status=jnp.where(placed_t, RUNNING, tasks.status).astype(
            tasks.status.dtype),
        host=jnp.where(placed_t, jnp.maximum(host_t, 0),
                       tasks.host).astype(tasks.host.dtype),
        first_start=jnp.where(placed_t, jnp.minimum(tasks.first_start, now),
                              tasks.first_start),
        speed=jnp.where(placed_t, sel_speed[slot_t], tasks.speed))


@pytest.mark.parametrize("k, n_tasks", [(64, 3000), (4096, 20000)])
@pytest.mark.parametrize("n_elig", ["fewer", "more"])
def test_commit_writes_the_placed_rows_like_the_rank_lookup(k, n_tasks,
                                                            n_elig):
    """The placed rows' writes by row index are bitwise the `[T]` rank
    lookup they replace, at the engine's 64 slots and Borg's 4,096: with
    fewer eligible rows than slots (a -1 tail) and with more (a bound that
    binds), slots that placed nothing among them, and rows that ran once
    before (a finite first start that `minimum` keeps)."""
    from repro.core.scheduler import _first_k_indices, commit_placements
    rng = np.random.default_rng(k + n_tasks)
    tasks = make_task_table(np.sort(rng.uniform(0.0, 10.0, n_tasks)),
                            rng.uniform(0.5, 4.0, n_tasks),
                            rng.integers(1, 5, n_tasks).astype(float))
    hosts = make_host_table(1534, 64, straggler_frac=0.3,
                            straggler_speed=0.5, seed=3)
    share = (k // 2 if n_elig == "fewer" else 2 * k) / n_tasks
    elig = jnp.asarray(rng.uniform(size=n_tasks) < share)
    ran = rng.uniform(size=n_tasks) < 0.2
    tasks = tasks._replace(
        status=jnp.where(elig, PENDING, RUNNING).astype(jnp.int32),
        host=jnp.where(elig, -1, rng.integers(0, 1534, n_tasks)).astype(
            jnp.int32),
        first_start=jnp.where(ran, rng.uniform(0.0, 3.0, n_tasks),
                              tasks.first_start).astype(jnp.float32))
    cand = _first_k_indices(elig, k)
    sel_host = jnp.where(
        (cand >= 0) & jnp.asarray(rng.uniform(size=k) < 0.8),
        jnp.asarray(rng.integers(0, 1534, k)), -1).astype(jnp.int32)
    now = jnp.float32(5.25)
    got = jax.jit(commit_placements)(tasks, hosts, now, cand, sel_host)
    want = jax.jit(_commit_by_rank, static_argnums=5)(
        tasks, hosts, now, elig, sel_host, k)
    for col in ("status", "host", "first_start", "speed"):
        np.testing.assert_array_equal(np.asarray(getattr(got, col)),
                                      np.asarray(getattr(want, col)),
                                      err_msg=col)
    placed = int((np.asarray(sel_host) >= 0).sum())
    assert int((np.asarray(got.status) != np.asarray(tasks.status)).sum()) \
        == placed > 0
    assert (int(np.asarray(elig).sum()) > k) == (n_elig == "more")
