"""Typed-workload subsystem tests: job classes, priorities, SLOs, traces.

Covers the demand-realism layer end to end: typed TaskTable columns and
their defaults, the priority-aware scatter-free scheduler, the shifting
gate's interactive bypass, per-class SLA/latency metrics (including the
exact sum-to-totals identity and fleet recombination), the tasktraces/
arrival-rate family, workload class mixes, and the `arrival_trace` /
`interactive_frac` grid plumbing.
"""
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DONE, INVALID, JOB_BATCH, JOB_INTERACTIVE,
                        JOB_TRAINING, PENDING, RUNNING, N_JOB_CLASSES,
                        SchedulerConfig, ShiftingConfig, SimConfig, dyn_axis,
                        fleet_totals, make_host_table, make_task_table,
                        pad_task_table, region_axis, retime_task_table,
                        simulate, summarize, sweep_grid, tasktrace_axis,
                        with_interactive_frac)
from repro.core.fleet import FleetSpec
from repro.core.power import (JOB_CLASS_CPU_UTIL, JOB_CLASS_GPU_UTIL,
                              class_utilization)
from repro.core.scheduler import (_first_k_by_priority,
                                  _first_k_by_priority_reference,
                                  _first_k_indices, schedule_first_fit,
                                  schedule_step)
from repro.core.shifting import should_stop, start_allowed
from repro.core.state import (init_sim_state, inverse_permutation,
                              permute_task_table, priority_schedule_order)
from repro.tasktraces import (make_arrival_rate_traces, make_arrival_sets,
                              sample_traffic_params, traffic_stats)
from repro.workloads.synthetic import make_workload

DT = 0.25


def flat_trace(n, value=100.0):
    return jnp.full((n,), value, jnp.float32)


@functools.cache
def _compiled(cfg):
    return jax.jit(lambda tasks, hosts, tr: simulate(tasks, hosts, tr, cfg))


def run(tasks, hosts, trace, cfg, dyn=None):
    if dyn is None:
        final, series = _compiled(cfg)(tasks, hosts, trace)
    else:
        final, series = simulate(tasks, hosts, trace, cfg, dyn=dyn)
    return summarize(final, cfg), final, series


def typed_table():
    """Nine tasks, three per class, all arriving early."""
    n = 9
    job_class = np.array([0, 1, 2] * 3, np.int32)
    return make_task_table(np.linspace(0.0, 2.0, n), np.full(n, 1.0),
                           np.ones(n), job_class=job_class)


class TestTypedTable:
    def test_untyped_defaults(self):
        t = make_task_table(np.zeros(4), np.ones(4), np.ones(4))
        assert np.all(np.asarray(t.job_class) == JOB_BATCH)
        assert np.all(np.asarray(t.priority) == 0)
        assert np.all(np.asarray(t.shiftable))
        assert np.all(np.asarray(t.sla_grace) == -1.0)

    def test_defaults_follow_job_class(self):
        t = typed_table()
        np.testing.assert_array_equal(np.asarray(t.priority),
                                      np.asarray(t.job_class))
        np.testing.assert_array_equal(
            np.asarray(t.shiftable),
            np.asarray(t.job_class) != JOB_INTERACTIVE)

    def test_pad_keeps_typed_columns(self):
        t = pad_task_table(typed_table(), 12)
        assert np.all(np.asarray(t.job_class)[9:] == JOB_BATCH)
        assert np.all(np.asarray(t.shiftable)[9:])
        assert np.all(np.asarray(t.sla_grace)[9:] == -1.0)
        np.testing.assert_array_equal(np.asarray(t.job_class)[:9],
                                      np.asarray(typed_table().job_class))

    def test_interactive_frac_zero_is_identity(self):
        t = typed_table()
        out = with_interactive_frac(t, jnp.float32(0.0), 0.25)
        for a, b in zip(t, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_interactive_frac_one_retypes_everything(self):
        t = typed_table()
        out = with_interactive_frac(t, jnp.float32(1.0), 0.25)
        assert np.all(np.asarray(out.job_class) == JOB_INTERACTIVE)
        assert not np.any(np.asarray(out.shiftable))
        np.testing.assert_allclose(np.asarray(out.sla_grace), 0.25)
        cpu, gpu = class_utilization(out.job_class)
        np.testing.assert_allclose(np.asarray(out.cpu_util),
                                   np.asarray(cpu))

    def test_retime(self):
        t = typed_table()
        arr = np.array([5.0, 1.0, 3.0, 2.0, 4.0, 0.5, 6.0, np.inf, 7.0],
                       np.float32)
        out = retime_task_table(t, arr)
        np.testing.assert_array_equal(np.asarray(out.arrival), arr)
        status = np.asarray(out.status)
        assert status[7] == INVALID
        assert np.all(status[np.isfinite(arr)] == PENDING)


class TestPriorityScheduler:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_first_k_by_priority_matches_reference(self, seed):
        rng = np.random.default_rng(seed)
        n, k, levels = 64, 12, 3
        mask = rng.uniform(size=n) < 0.4
        prio = rng.integers(0, levels, n)

        got = np.asarray(_first_k_by_priority(
            jnp.asarray(mask), jnp.asarray(prio, jnp.int32), k, levels))
        # reference: indices sorted by (priority desc, index asc), first k
        idx = np.nonzero(mask)[0]
        order = idx[np.lexsort((idx, -prio[idx]))][:k]
        expect = np.full(k, -1, np.int64)
        expect[:order.shape[0]] = order
        np.testing.assert_array_equal(got, expect)

    def test_levels_one_matches_plain_first_k(self):
        rng = np.random.default_rng(7)
        mask = jnp.asarray(rng.uniform(size=32) < 0.5)
        prio = jnp.zeros(32, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(_first_k_by_priority(mask, prio, 8, 1)),
            np.asarray(_first_k_indices(mask, 8)))

    def test_interactive_beats_fifo_under_contention(self):
        # one 1-core host; batch tasks listed (and arriving) first — with
        # priority levels the interactive task still starts first
        arrival = np.array([0.0, 0.0, 0.0])
        tasks = make_task_table(arrival, np.full(3, 1.0), np.ones(3),
                                job_class=np.array([0, 0, JOB_INTERACTIVE],
                                                   np.int32))
        hosts = make_host_table(1, 1)
        n = 40
        fifo = SimConfig(n_steps=n, scheduler=SchedulerConfig())
        prio = SimConfig(n_steps=n,
                         scheduler=SchedulerConfig(priority_levels=3))
        _, f_fifo, _ = run(tasks, hosts, flat_trace(n), fifo)
        _, f_prio, _ = run(tasks, hosts, flat_trace(n), prio)
        assert np.argmin(np.asarray(f_fifo.tasks.first_start)) == 0
        assert np.argmin(np.asarray(f_prio.tasks.first_start)) == 2

    def test_levels_one_is_bitwise_noop(self):
        # typed columns present but priority_levels=1: the untyped code
        # path runs and every result field is bit-for-bit unchanged
        n = 300
        tasks = make_task_table(np.linspace(0, 4, 24), np.full(24, 1.5),
                                np.ones(24) * 2)
        hosts = make_host_table(2, 4)
        cfg = SimConfig(n_steps=n)
        explicit = tasks._replace()  # same defaults, separate object
        r1, _, _ = run(tasks, hosts, flat_trace(n), cfg)
        r2, _, _ = run(explicit, hosts, flat_trace(n), cfg)
        for name in ("total_carbon_kg", "sla_violation_frac",
                     "mean_start_delay_h", "done_frac"):
            assert float(getattr(r1, name)) == float(getattr(r2, name))

    def test_aggregate_mode_rejects_priorities(self):
        tasks = typed_table()
        hosts = make_host_table(2, 4)
        cfg = SchedulerConfig(mode="aggregate", priority_levels=3)
        with pytest.raises(ValueError, match="aggregate"):
            schedule_step(tasks, hosts, jnp.float32(0.0),
                          jnp.ones(9, bool), cfg)


class TestShiftingBypass:
    def test_start_allowed_bypass(self):
        cfg = ShiftingConfig(enabled=True, max_delay_h=24.0)
        ci = jnp.float32(500.0)           # red
        thr = jnp.float32(100.0)
        arrival = jnp.zeros(3, jnp.float32)
        now = jnp.float32(1.0)
        shiftable = jnp.asarray([True, True, False])
        ok = start_allowed(ci, thr, now, arrival, cfg, shiftable=shiftable)
        np.testing.assert_array_equal(np.asarray(ok), [False, False, True])

    def test_should_stop_never_pauses_nonshiftable(self):
        cfg = ShiftingConfig(enabled=True, stop_running=True, max_delay_h=24.0)
        stop = should_stop(jnp.float32(500.0), jnp.float32(100.0),
                           jnp.float32(1.0), jnp.zeros(2, jnp.float32), cfg,
                           shiftable=jnp.asarray([True, False]))
        np.testing.assert_array_equal(np.asarray(stop), [True, False])

    def test_engine_interactive_starts_in_red_window(self):
        # carbon stays above the shifting threshold for the first 10 h:
        # batch waits, interactive (non-shiftable) starts immediately
        n = 200
        ci = np.full(n, 500.0, np.float32)
        ci[80:] = 10.0
        tasks = typed_table()
        hosts = make_host_table(4, 8)
        cfg = SimConfig(n_steps=n,
                        shifting=ShiftingConfig(enabled=True,
                                                max_delay_h=100.0),
                        scheduler=SchedulerConfig(priority_levels=3))
        res, final, _ = run(tasks, hosts, jnp.asarray(ci), cfg)
        delay = np.asarray(res.class_mean_start_delay_h)
        assert delay[JOB_INTERACTIVE] < 0.3
        assert delay[JOB_BATCH] > 5.0


class TestPerClassMetrics:
    def _mixed_run(self):
        n = 400
        rng = np.random.default_rng(11)
        job_class = rng.integers(0, 3, 64).astype(np.int32)
        tasks = make_task_table(np.sort(rng.uniform(0, 20, 64)),
                                rng.uniform(0.5, 4.0, 64),
                                rng.integers(1, 3, 64),
                                job_class=job_class)
        hosts = make_host_table(3, 4)
        cfg = SimConfig(n_steps=n,
                        scheduler=SchedulerConfig(priority_levels=3))
        return run(tasks, hosts, flat_trace(n), cfg)

    def test_class_counts_sum_to_totals(self):
        res, _, _ = self._mixed_run()
        np.testing.assert_allclose(
            float(jnp.sum(res.class_n_decided)), float(res.n_decided))
        np.testing.assert_allclose(
            float(jnp.sum(res.class_n_started)), float(res.n_started))
        viol_total = float(res.sla_violation_frac) * max(
            float(res.n_decided), 1.0)
        np.testing.assert_allclose(
            float(jnp.sum(res.class_n_violations)), viol_total, atol=1e-4)

    def test_fleet_totals_recombines_class_fields(self):
        res, _, _ = self._mixed_run()
        stacked = jax.tree.map(
            lambda x: jnp.stack([x, x]),
            res._replace(probes=None))
        agg = fleet_totals(stacked)
        assert agg.class_n_decided.shape == (N_JOB_CLASSES,)
        np.testing.assert_allclose(np.asarray(agg.class_n_decided),
                                   2 * np.asarray(res.class_n_decided))
        np.testing.assert_allclose(
            np.asarray(agg.class_sla_violation_frac),
            np.asarray(res.class_sla_violation_frac), rtol=1e-6)


def _summary_property_case(seed: int):
    """summarize() on a hand-built final state: exact class/total identity
    must hold for ANY status/finish configuration, not just reachable ones."""
    rng = np.random.default_rng(seed)
    n = 48
    tasks = make_task_table(
        rng.uniform(0, 10, n), rng.uniform(0.1, 5.0, n),
        rng.integers(1, 4, n),
        job_class=rng.integers(0, 3, n).astype(np.int32),
        sla_grace=rng.choice([-1.0, 0.25, 2.0], n))
    hosts = make_host_table(2, 4)
    cfg = SimConfig(n_steps=100)
    state = init_sim_state(tasks, hosts, 0)
    status = rng.choice([PENDING, RUNNING, DONE, INVALID], n,
                        p=[0.3, 0.2, 0.4, 0.1]).astype(np.int32)
    finish = np.where(status == DONE, rng.uniform(0.1, 30.0, n), np.inf)
    first_start = np.where(
        (status == DONE) | (status == RUNNING)
        | (rng.uniform(size=n) < 0.2),
        rng.uniform(0.0, 20.0, n), np.inf)
    state = state._replace(
        t=jnp.float32(25.0), step=jnp.int32(100),
        tasks=tasks._replace(status=jnp.asarray(status),
                             finish=jnp.asarray(finish, jnp.float32),
                             first_start=jnp.asarray(first_start,
                                                     jnp.float32)))
    return summarize(state, cfg)


try:
    from hypothesis import given, settings, strategies as st

    @given(st.integers(0, 10_000))
    @settings(max_examples=8, deadline=None)
    def test_class_counters_sum_exactly_hypothesis(seed):
        res = _summary_property_case(seed)
        assert float(jnp.sum(res.class_n_decided)) == float(res.n_decided)
        assert float(jnp.sum(res.class_n_started)) == float(res.n_started)
        viol = (float(res.sla_violation_frac)
                * max(float(res.n_decided), 1.0))
        assert abs(float(jnp.sum(res.class_n_violations)) - viol) < 1e-4
except ImportError:  # pragma: no cover - optional dependency
    def test_class_counters_sum_exactly_fallback():
        for seed in (0, 1, 2):
            res = _summary_property_case(seed)
            assert float(jnp.sum(res.class_n_decided)) == float(res.n_decided)


class TestTaskTraces:
    def test_shapes_positivity_determinism(self):
        r1 = make_arrival_rate_traces(400, DT, n_regions=6, seed=3)
        r2 = make_arrival_rate_traces(400, DT, n_regions=6, seed=3)
        assert r1.shape == (6, 400) and r1.dtype == np.float32
        assert np.all(r1 > 0)
        np.testing.assert_array_equal(r1, r2)

    def test_peak_to_trough_in_published_band(self):
        rates = make_arrival_rate_traces(96 * 14, DT, n_regions=32, seed=0)
        _, ratio = traffic_stats(rates)
        assert 2.5 < np.median(ratio) < 7.0

    def test_evening_peak_follows_carbon_phase(self):
        n = 96 * 14
        rates = make_arrival_rate_traces(n, DT, n_regions=24, seed=0)
        p = sample_traffic_params(24, 0)
        prof = rates.reshape(24, -1, 96).mean(axis=1)      # mean day [R, 96]
        local_peak = (np.argmax(prof, axis=1) * DT - p.phase_d) % 24.0
        # evening crest: median within a couple hours of 19:00 local
        assert 16.0 < np.median(local_peak) < 22.0

    def test_arrival_sets_sorted_and_density_tracks_curve(self):
        n_steps = 96 * 7
        rates = make_arrival_rate_traces(n_steps, DT, n_regions=4, seed=1)
        arr = make_arrival_sets(512, n_steps, DT, n_regions=4, seed=1,
                                rates=rates)
        assert arr.shape == (4, 512)
        assert np.all(np.diff(arr, axis=1) >= 0)
        assert np.all(arr >= 0) and np.all(arr <= n_steps * DT)
        # arrivals land proportionally to the rate mass: the busiest half
        # of each region's steps receives the majority of its arrivals
        for r in range(4):
            median_rate = np.median(rates[r])
            busy_mass = rates[r][rates[r] > median_rate].sum()
            steps = np.clip((arr[r] / DT).astype(int), 0, n_steps - 1)
            busy_arrivals = np.sum(rates[r][steps] > median_rate)
            assert busy_arrivals / 512 > 0.5 * busy_mass / rates[r].sum()


class TestWorkloadClassMix:
    def test_default_is_all_batch_and_unchanged(self):
        t0, _, _, _ = make_workload("surf", scale=0.02, n_tasks_cap=256,
                                    horizon_days=2.0)
        assert np.all(np.asarray(t0.job_class) == JOB_BATCH)
        assert np.all(np.asarray(t0.sla_grace) == -1.0)

    def test_class_mix_types_tasks(self):
        mix = (0.5, 0.3, 0.2)
        t, _, _, meta = make_workload("surf", scale=0.02, n_tasks_cap=512,
                                      horizon_days=3.0, class_mix=mix)
        cls = np.asarray(t.job_class)
        assert set(np.unique(cls)) == {0, 1, 2}
        assert meta["class_mix"] == pytest.approx(mix)
        # legacy draws untouched: arrival/cores identical to the untyped call
        t0, _, _, _ = make_workload("surf", scale=0.02, n_tasks_cap=512,
                                    horizon_days=3.0)
        np.testing.assert_array_equal(np.asarray(t.arrival),
                                      np.asarray(t0.arrival))
        np.testing.assert_array_equal(np.asarray(t.cores),
                                      np.asarray(t0.cores))
        # class consequences: durations scale, SLOs only on interactive
        d, d0 = np.asarray(t.duration), np.asarray(t0.duration)
        assert np.mean(d[cls == JOB_TRAINING]) > np.mean(d[cls == JOB_BATCH])
        assert (np.mean(d[cls == JOB_INTERACTIVE])
                < np.mean(d[cls == JOB_BATCH]))
        np.testing.assert_array_equal(d[cls == JOB_BATCH],
                                      d0[cls == JOB_BATCH])
        grace = np.asarray(t.sla_grace)
        assert np.all(grace[cls == JOB_INTERACTIVE] == 0.25)
        assert np.all(grace[cls != JOB_INTERACTIVE] == -1.0)
        np.testing.assert_allclose(
            np.asarray(t.cpu_util),
            np.asarray(JOB_CLASS_CPU_UTIL, np.float32)[cls])


class TestGridIntegration:
    def _setup(self):
        n = 96 * 3
        tasks = make_task_table(np.linspace(0, 8, 64), np.full(64, 1.0),
                                np.ones(64))
        hosts = make_host_table(3, 4)
        cfg = SimConfig(n_steps=n)
        return tasks, hosts, cfg, flat_trace(n)

    def test_tasktrace_axis_sweeps_arrivals(self):
        tasks, hosts, cfg, tr = self._setup()
        arr = make_arrival_sets(64, cfg.n_steps, DT, n_regions=3, seed=2)
        res = sweep_grid(tasks, hosts, cfg, [tasktrace_axis(arr)],
                         ci_trace=tr)
        assert np.asarray(res.op_carbon_kg).shape == (3,)
        # differential: each row equals a plain simulate with that arrival
        for r in range(3):
            ref, _, _ = run(tasks, hosts, tr, cfg,
                            dyn={"arrival_trace": jnp.asarray(arr[r])})
            np.testing.assert_allclose(float(res.op_carbon_kg[r]),
                                       float(ref.op_carbon_kg), rtol=1e-5)

    def test_tasktrace_width_mismatch_raises(self):
        tasks, hosts, cfg, tr = self._setup()
        arr = make_arrival_sets(32, cfg.n_steps, DT, n_regions=2, seed=2)
        with pytest.raises(ValueError, match="arrivals per point"):
            sweep_grid(tasks, hosts, cfg, [tasktrace_axis(arr)], ci_trace=tr)

    def test_tasktrace_rejects_region_axis(self):
        arr = make_arrival_sets(16, 96, DT, n_regions=2, seed=0)
        spec = FleetSpec(ci_traces=np.full((2, 96), 100.0, np.float32))
        with pytest.raises(ValueError, match="fleet"):
            from repro.core import ScenarioGrid
            ScenarioGrid([region_axis(spec), tasktrace_axis(arr)])

    def test_interactive_frac_grid_matches_loop(self):
        tasks, hosts, cfg, tr = self._setup()
        fracs = np.asarray([0.0, 0.5], np.float32)
        res = sweep_grid(tasks, hosts, cfg,
                         [dyn_axis(interactive_frac=fracs)], ci_trace=tr)
        for i, f in enumerate(fracs):
            ref, _, _ = run(tasks, hosts, tr, cfg,
                            dyn={"interactive_frac": jnp.float32(f)})
            np.testing.assert_allclose(
                np.asarray(res.class_n_started)[i],
                np.asarray(ref.class_n_started), rtol=1e-5)

    def test_interactive_frac_zero_matches_plain_run(self):
        tasks, hosts, cfg, tr = self._setup()
        plain, _, _ = run(tasks, hosts, tr, cfg)
        frac0, _, _ = run(tasks, hosts, tr, cfg,
                          dyn={"interactive_frac": jnp.float32(0.0)})
        assert float(plain.op_carbon_kg) == float(frac0.op_carbon_kg)
        assert float(plain.sla_violation_frac) == float(
            frac0.sla_violation_frac)


class TestSinglePassScheduler:
    """Differential pins for the ISSUE-10 single-pass priority select and
    the presorted demand-scan path (hypothesis twins live in
    tests/test_core_properties.py; these run in the base tier)."""

    @pytest.mark.parametrize("seed", range(6))
    def test_single_pass_matches_per_level_reference(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 96))
        levels = int(rng.integers(1, 6))
        k = int(rng.integers(1, 2 * n + 1))
        mask = rng.uniform(size=n) < rng.uniform()
        # out-of-range codes match no level and must never be selected
        prio = rng.integers(-1, levels + 1, n)
        got = np.asarray(_first_k_by_priority(
            jnp.asarray(mask), jnp.asarray(prio, jnp.int32), k, levels))
        ref = np.asarray(_first_k_by_priority_reference(
            jnp.asarray(mask), jnp.asarray(prio, jnp.int32), k, levels))
        np.testing.assert_array_equal(got, ref)
        idx = np.nonzero(mask & (prio >= 0) & (prio < levels))[0]
        order = idx[np.lexsort((idx, -prio[idx]))][:k]
        expect = np.full(k, -1, np.int64)
        expect[:order.shape[0]] = order
        np.testing.assert_array_equal(got, expect)

    @staticmethod
    def _case(seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 48))
        levels = int(rng.integers(2, 5))
        tasks = make_task_table(
            np.sort(rng.uniform(0.0, 12.0, n)), rng.uniform(0.5, 6.0, n),
            rng.integers(1, 4, n).astype(float),
            priority=rng.integers(0, levels, n).astype(np.int32))
        cfg = SchedulerConfig(slots_per_step=int(rng.integers(1, 17)),
                              priority_levels=levels)
        now = jnp.float32(rng.uniform(0.0, 14.0))
        return tasks, cfg, now

    @pytest.mark.parametrize("seed", range(6))
    def test_presorted_matches_level_major(self, seed):
        """Permute once + plain-FIFO prefix (the engine's presorted path)
        is bit-for-bit the per-step level-major flatten."""
        tasks, cfg, now = self._case(seed)
        hosts = make_host_table(int(seed % 3) + 1, 4)
        ok = jnp.ones(tasks.n, bool)
        plain, *_ = schedule_first_fit(tasks, hosts, now, ok, cfg)
        order = priority_schedule_order(tasks, cfg.priority_levels)
        pre, *_ = schedule_first_fit(permute_task_table(tasks, order),
                                       hosts, now, ok[order], cfg,
                                       presorted=True)
        pre = permute_task_table(pre, inverse_permutation(order))
        for name in ("status", "host", "first_start", "remaining"):
            np.testing.assert_array_equal(
                np.asarray(getattr(plain, name)),
                np.asarray(getattr(pre, name)), name)

    @pytest.mark.parametrize("seed", range(6))
    def test_admission_exactly_once_and_level_ordered(self, seed):
        """With capacity unconstrained the admitted set is EXACTLY the
        first-k prefix of (priority desc, arrival): each eligible row at
        most once, higher classes never displaced by lower ones."""
        tasks, cfg, now = self._case(seed)
        hosts = make_host_table(1, 10_000)  # capacity never binds
        out, *_ = schedule_first_fit(tasks, hosts, now,
                                       jnp.ones(tasks.n, bool), cfg)
        placed = np.asarray(out.status) == RUNNING
        elig = np.asarray(tasks.arrival) <= float(now)
        idx = np.nonzero(elig)[0]
        prio = np.asarray(tasks.priority)
        expect = np.zeros_like(placed)
        expect[idx[np.lexsort((idx, -prio[idx]))][:cfg.slots_per_step]] = True
        np.testing.assert_array_equal(placed, expect)
        assert np.all(np.asarray(out.host)[placed] == 0)
        assert np.all(np.asarray(out.first_start)[placed] == float(now))
        assert np.all(~np.isfinite(np.asarray(out.first_start)[~placed]))


def _collect_scans(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                if isinstance(x, jax.extend.core.ClosedJaxpr):
                    _collect_scans(x.jaxpr, out)
                elif isinstance(x, jax.extend.core.Jaxpr):
                    _collect_scans(x, out)
    return out


def test_typed_vmap_demand_scan_is_batched():
    """vmap over carbon traces must BATCH the typed demand scan (one scan
    over time with a batched carry), never rewrite it into a loop over the
    batch axis — the per-cell fallback behind the ISSUE-10 typed-vmap16
    collapse."""
    n_steps, batch = 96, 5
    rng = np.random.default_rng(0)
    tasks = make_task_table(np.sort(rng.uniform(0, 12, 12)),
                            rng.uniform(0.5, 4.0, 12),
                            rng.integers(1, 3, 12).astype(float),
                            job_class=rng.integers(0, 3, 12).astype(np.int32))
    hosts = make_host_table(3, 4)
    cfg = SimConfig(n_steps=n_steps,
                    shifting=ShiftingConfig(enabled=True, max_delay_h=24.0),
                    scheduler=SchedulerConfig(priority_levels=3))
    traces = jnp.asarray(
        np.abs(300.0 * (1 + 0.4 * rng.standard_normal((batch, n_steps)))),
        jnp.float32)
    jaxpr = jax.make_jaxpr(
        jax.vmap(lambda tr: simulate(tasks, hosts, tr, cfg)))(traces)
    scans = _collect_scans(jaxpr.jaxpr, [])
    assert all(e.params["length"] != batch for e in scans)
    step_scans = [e for e in scans if e.params["length"] == n_steps]
    assert step_scans, "demand scan missing from vmapped jaxpr"

    def batched_carry(e):
        nc, ncar = e.params["num_consts"], e.params["num_carry"]
        carry = e.params["jaxpr"].jaxpr.invars[nc:nc + ncar]
        return any(batch in getattr(v.aval, "shape", ()) for v in carry)

    assert any(batched_carry(e) for e in step_scans)
