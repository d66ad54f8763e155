"""Equivalence tests for the N-dimensional scenario-grid engine (core/grid.py).

The contract: one compiled grid program == the nested Python loop of
per-scenario `simulate()` calls, to <=1e-5 relative error, for every axis
kind (trace / dyn / seed) and every execution mode (plain, chunked, sharded).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (BatteryConfig, CoolingConfig, FailureConfig,
                        ScenarioGrid, SchedulerConfig, ShiftingConfig,
                        SimConfig, dyn_axis,
                        make_host_table, make_task_table, seed_axis, simulate,
                        summarize, sweep_grid, trace_axis, weather_axis,
                        with_scale)

N_STEPS = 96  # 1 day at dt=0.25 — equivalence needs axis coverage, not horizon


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(0)
    n = 12
    tasks = make_task_table(np.sort(rng.uniform(0.0, 6.0, n)),
                            rng.uniform(0.5, 4.0, n),
                            rng.integers(1, 3, n).astype(float))
    hosts = make_host_table(3, 4)
    return tasks, hosts


@pytest.fixture(scope="module")
def traces():
    t = np.arange(N_STEPS) * 0.25
    return np.stack([300.0 + 200.0 * np.sin(2 * np.pi * t / 24.0 + p)
                     for p in (0.0, 1.7)]).astype(np.float32)


def _loop_ref(tasks, hosts, trace, cfg):
    return summarize(simulate(tasks, hosts, trace, cfg)[0], cfg)


def _assert_cell_close(res, idx, ref, rtol=1e-5):
    for field, want in zip(res._fields, ref):
        if getattr(res, field) is None:  # SimResult.probes is None unless cfg.probes.enabled
            continue
        got = np.asarray(getattr(res, field))[idx]
        np.testing.assert_allclose(got, np.asarray(want), rtol=rtol,
                                   atol=1e-6, err_msg=f"{field} at {idx}")


class TestGridMatchesLoop:
    def test_regions_x_capacity_x_quantile(self, workload, traces):
        """The acceptance grid: 3 axes, one program, <=1e-5 vs simulate()."""
        tasks, hosts = workload
        caps = np.array([2.0, 6.0], np.float32)
        quants = np.array([0.25, 0.6], np.float32)
        cfg = SimConfig(n_steps=N_STEPS,
                        battery=BatteryConfig(enabled=True),
                        shifting=ShiftingConfig(enabled=True))
        res = sweep_grid(tasks, hosts, cfg, [
            trace_axis(traces),
            dyn_axis(batt_capacity_kwh=caps),
            dyn_axis(shift_quantile_value=quants),
        ])
        assert res.total_carbon_kg.shape == (2, 2, 2)
        for r in range(2):
            for c in range(2):
                for q in range(2):
                    cfg_l = cfg.replace(
                        battery=BatteryConfig(enabled=True,
                                              capacity_kwh=float(caps[c])),
                        shifting=ShiftingConfig(enabled=True,
                                                quantile=float(quants[q])))
                    ref = _loop_ref(tasks, hosts, traces[r], cfg_l)
                    _assert_cell_close(res, (r, c, q), ref)

    def test_seed_and_scaling_axes(self, workload, traces):
        """seed_axis drives the failure PRNG; n_active_hosts drives HS."""
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS,
                        failures=FailureConfig(enabled=True, mtbf_h=30.0))
        n_active = np.array([1, 2, 3])
        seeds = [0, 7]
        res = sweep_grid(tasks, hosts, cfg,
                         [dyn_axis(n_active_hosts=n_active), seed_axis(seeds)],
                         ci_trace=traces[0])
        assert res.total_carbon_kg.shape == (3, 2)
        for i, n in enumerate(n_active):
            for j, s in enumerate(seeds):
                cfg_l = cfg.replace(seed=int(s))
                ref = _loop_ref(tasks, with_scale(hosts, int(n)), traces[0],
                                cfg_l)
                _assert_cell_close(res, (i, j), ref)

    def test_weather_axis_matches_loop(self, workload, traces):
        """Climate x CI-region x setpoint grid == per-scenario simulate()
        with the same weather trace and setpoint (acceptance criterion)."""
        from repro.weathertraces.synthetic import make_weather_traces
        tasks, hosts = workload
        wb = make_weather_traces(N_STEPS, 0.25, 3, seed=2)
        setpoints = np.array([20.0, 26.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS,
                        cooling=CoolingConfig(enabled=True),
                        battery=BatteryConfig(enabled=True))
        res = sweep_grid(tasks, hosts, cfg, [
            weather_axis(wb),
            trace_axis(traces),
            dyn_axis(cooling_setpoint=setpoints),
        ])
        assert res.pue.shape == (3, 2, 2)
        assert (np.asarray(res.pue) >= 1.0).all()
        for w in range(3):
            for r in range(2):
                for s in range(2):
                    final, _ = simulate(
                        tasks, hosts, traces[r], cfg,
                        dyn={"cooling_setpoint": setpoints[s]},
                        weather_trace=wb[w])
                    _assert_cell_close(res, (w, r, s), summarize(final, cfg))

    def test_zipped_dyn_axis(self, workload, traces):
        """Two names in one dyn_axis sweep zipped (one dim, not a product)."""
        tasks, hosts = workload
        caps = np.array([3.0, 8.0], np.float32)
        rates = np.array([6.0, 10.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        res = sweep_grid(tasks, hosts, cfg,
                         [dyn_axis(batt_capacity_kwh=caps, batt_rate_kw=rates)],
                         ci_trace=traces[0])
        assert res.total_carbon_kg.shape == (2,)
        for i in range(2):
            final, _ = simulate(tasks, hosts, traces[0], cfg,
                                dyn={"batt_capacity_kwh": caps[i],
                                     "batt_rate_kw": rates[i]})
            _assert_cell_close(res, (i,), summarize(final, cfg))


class TestExecutionModes:
    def test_chunked_matches_unchunked(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([2.0, 4.0, 6.0], np.float32)  # ragged tail at chunk=2
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        axes = [dyn_axis(batt_capacity_kwh=caps), trace_axis(traces)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        chunked = sweep_grid(tasks, hosts, cfg, axes, chunk_size=2)
        assert chunked.total_carbon_kg.shape == (3, 2)
        for field in full._fields:
            if getattr(full, field) is None:  # probes: off by default
                continue
            np.testing.assert_allclose(np.asarray(getattr(chunked, field)),
                                       np.asarray(getattr(full, field)),
                                       rtol=1e-6, err_msg=field)

    def test_sharded_matches_unsharded(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([2.0, 6.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        axes = [trace_axis(traces), dyn_axis(batt_capacity_kwh=caps)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        sharded = sweep_grid(tasks, hosts, cfg, axes, mesh=mesh)
        for field in full._fields:
            if getattr(full, field) is None:  # probes: off by default
                continue
            np.testing.assert_allclose(np.asarray(getattr(sharded, field)),
                                       np.asarray(getattr(full, field)),
                                       rtol=1e-6, err_msg=field)

    def test_weather_grid_chunked_and_sharded(self, workload, traces):
        """The acceptance grid with cooling on: climate x region x battery in
        ONE program; chunked and sharded execution agree with it."""
        from repro.weathertraces.synthetic import make_weather_traces
        tasks, hosts = workload
        wb = make_weather_traces(N_STEPS, 0.25, 3, seed=5)
        caps = np.array([2.0, 6.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS,
                        cooling=CoolingConfig(enabled=True),
                        battery=BatteryConfig(enabled=True))
        axes = [weather_axis(wb), trace_axis(traces),
                dyn_axis(batt_capacity_kwh=caps)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        assert full.pue.shape == (3, 2, 2)
        chunked = sweep_grid(tasks, hosts, cfg, axes, chunk_size=2)
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        sharded = sweep_grid(tasks, hosts, cfg, axes, mesh=mesh)
        for field in full._fields:
            if getattr(full, field) is None:  # probes: off by default
                continue
            want = np.asarray(getattr(full, field))
            np.testing.assert_allclose(np.asarray(getattr(chunked, field)),
                                       want, rtol=1e-6, err_msg=field)
            np.testing.assert_allclose(np.asarray(getattr(sharded, field)),
                                       want, rtol=1e-6, err_msg=field)

    def test_sharded_chunked_multidevice(self):
        """mesh + chunk_size with chunks NOT divisible by the device count:
        chunks must round up to a device multiple instead of crashing.
        Runs in a subprocess to force a 4-device host platform."""
        import os
        import subprocess
        import sys
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import (SimConfig, BatteryConfig, sweep_grid, trace_axis,
                        dyn_axis, make_host_table, make_task_table)
tasks = make_task_table([0.0, 1.0], [2.0, 2.0], [2.0, 2.0])
hosts = make_host_table(2, 4)
S = 48
t = np.arange(S) * 0.25
traces = np.stack([300 + 100 * np.sin(2 * np.pi * t / 24 + p)
                   for p in np.linspace(0, 3, 8)]).astype(np.float32)
cfg = SimConfig(n_steps=S, battery=BatteryConfig(enabled=True))
mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
axes = [trace_axis(traces),
        dyn_axis(batt_capacity_kwh=np.array([2.0, 5.0], np.float32))]
full = sweep_grid(tasks, hosts, cfg, axes)
for cs in (3, 4, 6):   # ragged vs device count, exact, tail-producing
    got = sweep_grid(tasks, hosts, cfg, axes, mesh=mesh, chunk_size=cs)
    assert np.allclose(np.asarray(got.total_carbon_kg),
                       np.asarray(full.total_carbon_kg)), cs
print("OK")
"""
        env = dict(os.environ, PYTHONPATH=os.path.join(
            os.path.dirname(__file__), "..", "src"))
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=300,
                             env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().endswith("OK")


class TestReductions:
    def test_min_and_argmin_match_materialized_grid(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([1.0, 4.0, 8.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        axes = [trace_axis(traces), dyn_axis(batt_capacity_kwh=caps)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        mn = sweep_grid(tasks, hosts, cfg, axes, reduce=("min", 1))
        am = sweep_grid(tasks, hosts, cfg, axes, reduce=("argmin", -1))
        assert mn.total_carbon_kg.shape == (2,)
        for field in full._fields:
            if getattr(full, field) is None:  # probes: off by default
                continue
            got = np.asarray(getattr(full, field))
            np.testing.assert_allclose(np.asarray(getattr(mn, field)),
                                       got.min(axis=1), rtol=1e-6,
                                       err_msg=field)
            np.testing.assert_array_equal(np.asarray(getattr(am, field)),
                                          got.argmin(axis=1), field)

    def test_reduce_leading_axis_unchunked(self, workload, traces):
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS)
        axes = [trace_axis(traces)]
        red = sweep_grid(tasks, hosts, cfg, axes, reduce=("max", 0))
        full = sweep_grid(tasks, hosts, cfg, axes)
        np.testing.assert_allclose(np.asarray(red.total_carbon_kg),
                                   np.asarray(full.total_carbon_kg).max(),
                                   rtol=1e-6)

    def test_reduce_chunked_trailing_axis(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([1.0, 4.0, 8.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        axes = [dyn_axis(batt_capacity_kwh=caps), trace_axis(traces)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        red = sweep_grid(tasks, hosts, cfg, axes, chunk_size=2,
                         reduce=("min", 1))
        np.testing.assert_allclose(np.asarray(red.total_carbon_kg),
                                   np.asarray(full.total_carbon_kg).min(axis=1),
                                   rtol=1e-6)

    def test_reduce_leading_axis_chunked_rejected(self, workload, traces):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="leading axis"):
            sweep_grid(*workload, SimConfig(n_steps=N_STEPS),
                       [trace_axis(traces)], chunk_size=1,
                       reduce=("min", 0))

    def test_bad_reduce_specs_rejected(self, workload, traces):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="unknown reduce op"):
            sweep_grid(tasks, hosts, SimConfig(n_steps=N_STEPS),
                       [trace_axis(traces)], reduce=("median", 0))
        with pytest.raises(ValueError, match="out of range"):
            sweep_grid(tasks, hosts, SimConfig(n_steps=N_STEPS),
                       [trace_axis(traces)], reduce=("min", 2))


class TestAutoChunking:
    def test_under_budget_runs_unchunked_and_matches(self, workload, traces):
        """Default (no chunk_size): small grids fit the budget and match the
        explicit-chunk result."""
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS)
        axes = [trace_axis(traces)]
        grid = ScenarioGrid(axes)
        auto = grid._auto_chunk_size(tasks, hosts, cfg, None)
        assert auto == 2  # whole leading axis: unchunked
        full = sweep_grid(tasks, hosts, cfg, axes)
        assert full.total_carbon_kg.shape == (2,)

    def test_tiny_budget_forces_chunking_same_result(self, workload, traces):
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS)
        axes = [trace_axis(traces)]
        full = sweep_grid(tasks, hosts, cfg, axes)
        # a 1-byte budget clamps to chunk_size 1: 2 programs, same numbers
        chunked = sweep_grid(tasks, hosts, cfg, axes, memory_budget_bytes=1.0)
        for field in full._fields:
            if getattr(full, field) is None:  # probes: off by default
                continue
            np.testing.assert_allclose(np.asarray(getattr(chunked, field)),
                                       np.asarray(getattr(full, field)),
                                       rtol=1e-6, err_msg=field)


class TestLowerGrid:
    def test_lower_arbitrary_grid_and_analyze(self, workload, traces):
        """ANY declared grid lowers to one program (no allocation, no run)
        whose compiled HLO feeds the roofline analyzer."""
        from repro.launch import hlo_analysis
        tasks, hosts = workload
        caps = np.array([1.0, 4.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        grid = ScenarioGrid([trace_axis(traces),
                             dyn_axis(batt_capacity_kwh=caps)])
        lowered = grid.lower(tasks, hosts, cfg)
        stats = hlo_analysis.analyze(lowered.compile().as_text())
        assert stats["bytes"] > 0

    def test_lower_sharded_with_reduction(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([1.0, 4.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        grid = ScenarioGrid([trace_axis(traces),
                             dyn_axis(batt_capacity_kwh=caps)])
        lowered = grid.lower(tasks, hosts, cfg, mesh=mesh,
                             reduce=("argmin", 1))
        assert "argmin" in lowered.as_text() or lowered.compile() is not None

    def test_legacy_lower_sweep_delegates(self, workload):
        from repro.core import lower_sweep
        tasks, hosts = workload
        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        lowered = lower_sweep(mesh, tasks, hosts, SimConfig(n_steps=N_STEPS),
                              n_regions=4, n_steps=N_STEPS)
        assert lowered.compile() is not None


class TestValidation:
    def test_duplicate_axis_name_rejected(self, traces):
        with pytest.raises(ValueError, match="declared twice"):
            sweep_grid(None, None, SimConfig(), [
                dyn_axis(batt_capacity_kwh=np.ones(2)),
                dyn_axis(batt_capacity_kwh=np.ones(3))])

    def test_missing_trace_rejected(self, workload):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="pass ci_trace"):
            sweep_grid(tasks, hosts, SimConfig(),
                       [dyn_axis(batt_capacity_kwh=np.ones(2))])

    def test_trace_axis_and_ci_trace_conflict(self, workload, traces):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="trace_axis"):
            sweep_grid(tasks, hosts, SimConfig(), [trace_axis(traces)],
                       ci_trace=traces[0])

    def test_zip_length_mismatch_rejected(self):
        with pytest.raises(ValueError, match="disagree on length"):
            dyn_axis(batt_capacity_kwh=np.ones(2), batt_rate_kw=np.ones(3))

    def test_base_dyn_shadowing_rejected(self, workload, traces):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="shadow"):
            sweep_grid(tasks, hosts, SimConfig(),
                       [trace_axis(traces),
                        dyn_axis(batt_capacity_kwh=np.ones(2))],
                       dyn={"batt_capacity_kwh": 3.0})

    def test_weather_axis_without_cooling_rejected(self, workload, traces):
        tasks, hosts = workload
        with pytest.raises(ValueError, match="cooling.enabled"):
            sweep_grid(tasks, hosts, SimConfig(n_steps=N_STEPS),
                       [weather_axis(traces)], ci_trace=traces[0])


class TestShardMapExecutor:
    """The ISSUE-10 weak-scaling executor: one leading-axis chunk per
    device via shard_map.  Acceptance pin: at device_count=1 it is
    BITWISE-equal to the chunked path."""

    def test_matches_chunked_bitwise_single_device(self, workload, traces):
        tasks, hosts = workload
        caps = np.array([2.0, 6.0], np.float32)
        cfg = SimConfig(n_steps=N_STEPS, battery=BatteryConfig(enabled=True))
        axes = [trace_axis(np.concatenate([traces, traces * 0.8])),
                dyn_axis(batt_capacity_kwh=caps)]
        chunked = sweep_grid(tasks, hosts, cfg, axes, chunk_size=4)
        weak = sweep_grid(tasks, hosts, cfg, axes, executor="shard_map")
        for field in chunked._fields:
            if getattr(chunked, field) is None:  # probes: off by default
                continue
            np.testing.assert_array_equal(
                np.asarray(getattr(weak, field)),
                np.asarray(getattr(chunked, field)), err_msg=field)

    def test_typed_grid_matches_bitwise(self, workload, traces):
        """The weak-scaling bench's typed variant: priority levels +
        shifting + the interactive_frac dyn key, same bitwise pin."""
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS,
                        shifting=ShiftingConfig(enabled=True,
                                                max_delay_h=24.0),
                        scheduler=SchedulerConfig(priority_levels=3))
        axes = [trace_axis(traces)]
        dyn = {"interactive_frac": np.float32(0.35)}
        chunked = sweep_grid(tasks, hosts, cfg, axes, dyn=dyn)
        grid = ScenarioGrid(axes, base_dyn=dyn)
        weak = grid.run_shard_map(tasks, hosts, cfg)
        for field in chunked._fields:
            if getattr(chunked, field) is None:
                continue
            np.testing.assert_array_equal(
                np.asarray(getattr(weak, field)),
                np.asarray(getattr(chunked, field)), err_msg=field)

    def test_rejects_chunk_size_and_unknown_executor(self, workload, traces):
        tasks, hosts = workload
        cfg = SimConfig(n_steps=N_STEPS)
        axes = [trace_axis(traces)]
        with pytest.raises(ValueError, match="one chunk per"):
            sweep_grid(tasks, hosts, cfg, axes, executor="shard_map",
                       chunk_size=1)
        with pytest.raises(ValueError, match="unknown executor"):
            sweep_grid(tasks, hosts, cfg, axes, executor="pmap")

    def test_rejects_region_leading_axis(self, workload, traces):
        from repro.core import region_axis
        from repro.core.fleet import FleetSpec
        grid = ScenarioGrid([region_axis(FleetSpec(ci_traces=traces))])
        tasks, hosts = workload
        with pytest.raises(ValueError, match="region_axis"):
            grid.shard_map_callable(tasks, hosts, SimConfig(n_steps=N_STEPS))

    def test_multidevice_weak_scaling(self):
        """4 forced host devices: the cells land on all four, divisibility
        is enforced, and results are bitwise equal to the single-program
        path except the carbon sums, which agree to one ulp.  Subprocess:
        device count is fixed at backend init."""
        import os
        import subprocess
        import sys
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import (SimConfig, BatteryConfig, SchedulerConfig,
                        ShiftingConfig, sweep_grid, trace_axis, dyn_axis,
                        make_host_table, make_task_table)
rng = np.random.default_rng(0)
tasks = make_task_table(np.sort(rng.uniform(0, 6, 12)),
                        rng.uniform(0.5, 4.0, 12),
                        rng.integers(1, 3, 12).astype(float),
                        job_class=rng.integers(0, 3, 12).astype(np.int32))
hosts = make_host_table(3, 4)
S = 48
t = np.arange(S) * 0.25
traces = np.stack([300 + 100 * np.sin(2 * np.pi * t / 24 + p)
                   for p in np.linspace(0, 3, 8)]).astype(np.float32)
cfg = SimConfig(n_steps=S, battery=BatteryConfig(enabled=True),
                shifting=ShiftingConfig(enabled=True, max_delay_h=24.0),
                scheduler=SchedulerConfig(priority_levels=3))
axes = [trace_axis(traces)]
full = sweep_grid(tasks, hosts, cfg, axes)
weak = sweep_grid(tasks, hosts, cfg, axes, executor="shard_map")
assert len(weak.total_carbon_kg.sharding.device_set) == 4
for f in full._fields:
    a = getattr(full, f)
    if a is None:
        continue
    if f in ("op_carbon_kg", "total_carbon_kg"):
        # XLA:CPU rounds the partitioned program's S-step carbon sum
        # differently in the last place: one ulp, not bitwise
        np.testing.assert_array_max_ulp(np.asarray(a),
                                        np.asarray(getattr(weak, f)), 1)
        continue
    assert np.array_equal(np.asarray(a), np.asarray(getattr(weak, f))), f
try:  # 6 cells over 4 devices: must refuse, not pad silently
    sweep_grid(tasks, hosts, cfg, [trace_axis(traces[:6])],
               executor="shard_map")
except ValueError as e:
    assert "divide evenly" in str(e)
else:
    raise SystemExit("indivisible lead not rejected")
print("OK")
"""
        env = dict(os.environ, PYTHONPATH=os.path.join(
            os.path.dirname(__file__), "..", "src"))
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=300,
                             env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().endswith("OK")
