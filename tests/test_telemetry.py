"""Telemetry subsystem tier (core/telemetry.py).

Four contracts:
  1. Zero overhead when disabled (the default): results are bitwise
     identical with a telemetry session on or off, and `SimResult.probes`
     stays None so goldens and downstream pytrees never change shape.
  2. The probe bus is backend-equivalent: the stage pipeline's in-scan
     ring buffer and the megakernel's vectorized gather produce the same
     samples (steps bitwise, values to the backends' float tolerance),
     including strides and ring wrap-around.
  3. The recompile detector turns a sweep that compiles per cell into a
     warning/failure, without false positives on cached re-execution.
  4. RunRecords are structured and durable: JSONL rows round-trip and
     carry the compile-vs-execute split and the chunk plan.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatteryConfig, CoolingConfig, PricingConfig,
                        ProbeConfig, RenewableConfig, ResilienceConfig,
                        SimConfig, dyn_axis,
                        make_host_table, make_task_table, simulate,
                        simulate_fleet, summarize, sweep_grid, telemetry,
                        trace_axis)
from repro.core.fleet import FleetSpec

S = 96
DT = 0.25

rng0 = np.random.default_rng(33)
N = 12
TASKS = make_task_table(np.sort(rng0.uniform(0.0, 8.0, N)),
                        rng0.uniform(0.5, 4.0, N),
                        rng0.integers(1, 3, N).astype(float))
HOSTS = make_host_table(3, 4)


def _traces(seed):
    rng = np.random.default_rng(seed)
    t = np.arange(S) * DT
    ci = (250 + 150 * np.sin(2 * np.pi * t / 24 + rng.uniform(0, 6))
          + rng.normal(0, 10, S)).clip(5.0).astype(np.float32)
    price = (0.12 * (1 + 0.8 * np.sin(2 * np.pi * t / 24))
             + rng.exponential(0.01, S)).clip(0.005).astype(np.float32)
    wb = (14 + 6 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    cf = np.clip(np.sin(2 * np.pi * (t - 6.0) / 24.0), 0.0,
                 1.0).astype(np.float32)
    return ci, price, wb, cf


CI, PRICE, WB, CF = _traces(5)


def _cfg(cool=False, price=False, renew=False, batt=True, **kw):
    base = dict(
        n_steps=S,
        cooling=CoolingConfig(enabled=cool),
        pricing=PricingConfig(enabled=price, billing_window_h=12.0),
        renewables=RenewableConfig(enabled=renew, pv_capacity_kw=25.0),
        battery=BatteryConfig(enabled=batt, capacity_kwh=6.0))
    base.update(kw)
    return SimConfig(**base)


def _dyn(cfg):
    d = {}
    if cfg.pricing.enabled:
        d["price_trace"] = jnp.asarray(PRICE)
    if cfg.cooling.enabled:
        d["wet_bulb_trace"] = jnp.asarray(WB)
    if cfg.renewables.enabled:
        d["pv_cf_trace"] = jnp.asarray(CF)
    return d


def _run(cfg):
    final, _ = simulate(TASKS, HOSTS, CI, cfg, dyn=_dyn(cfg))
    return summarize(final, cfg)


# ---------------------------------------------------------------------------
# 1. disabled by default + bitwise identity when enabled
# ---------------------------------------------------------------------------

class TestDisabledPath:
    def test_disabled_by_default(self):
        assert not telemetry.enabled()
        assert telemetry.get() is None
        res = _run(_cfg())
        assert res.probes is None

    def test_scopes_are_null_contexts_when_disabled(self):
        import contextlib
        assert isinstance(telemetry.span("x"), contextlib.nullcontext)
        assert isinstance(telemetry.stage_scope("x"),
                          contextlib.nullcontext)

    def test_enabled_session_is_bitwise_identical(self, tmp_path):
        """Spans only measure host time: enabling telemetry must not move a
        single bit of any result (the goldens tier runs with telemetry off;
        this pins the ON path to it)."""
        cfg = _cfg(cool=True, price=True, renew=True)
        base = _run(cfg)
        base_mk = _run(cfg.replace(backend="megakernel", use_pallas=True))
        with telemetry.session(out_dir=str(tmp_path)):
            inst = _run(cfg)
            inst_mk = _run(cfg.replace(backend="megakernel",
                                       use_pallas=True))
        assert not telemetry.enabled()
        for f in base._fields:
            if getattr(base, f) is None:
                continue
            np.testing.assert_array_equal(
                np.asarray(getattr(base, f)),
                np.asarray(getattr(inst, f)), err_msg=f)
            np.testing.assert_array_equal(
                np.asarray(getattr(base_mk, f)),
                np.asarray(getattr(inst_mk, f)), err_msg=f)

    def test_grid_sweep_identical_with_and_without_session(self, tmp_path):
        cfg = _cfg()
        caps = np.array([2.0, 6.0, 12.0], np.float32)
        axes = [dyn_axis(batt_capacity_kwh=caps)]
        plain = sweep_grid(TASKS, HOSTS, cfg, axes, CI)
        with telemetry.session(out_dir=str(tmp_path)):
            inst = sweep_grid(TASKS, HOSTS, cfg, axes, CI)
        for f in plain._fields:
            if getattr(plain, f) is None:
                continue
            np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                          np.asarray(getattr(inst, f)),
                                          err_msg=f)


# ---------------------------------------------------------------------------
# spans + chrome trace export
# ---------------------------------------------------------------------------

class TestSpans:
    def test_session_exports_valid_chrome_trace(self, tmp_path):
        with telemetry.session(out_dir=str(tmp_path)) as tel:
            with tel.span("outer", detail="unit"):
                _run(_cfg())
            assert tel.span_durations("outer")
        path = os.path.join(str(tmp_path), "trace.json")
        assert os.path.exists(path)
        with open(path) as f:
            trace = json.load(f)
        assert "traceEvents" in trace and trace["traceEvents"]
        names = {e["name"] for e in trace["traceEvents"]}
        assert "outer" in names and "simulate" in names
        for ev in trace["traceEvents"]:
            assert ev["ph"] == "X"
            assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0

    def test_grid_run_emits_build_and_chunk_spans(self, tmp_path):
        cfg = _cfg()
        caps = np.array([2.0, 6.0, 12.0, 20.0], np.float32)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            sweep_grid(TASKS, HOSTS, cfg, [dyn_axis(batt_capacity_kwh=caps)],
                       CI, chunk_size=2)
            names = [e["name"] for e in tel.events]
        assert "grid.build" in names
        assert names.count("grid.chunk") == 2

    def test_span_reaches_the_profiler_host_plane(self, tmp_path):
        """A host span is also a profiler TraceAnnotation, so a profiled
        run's idle gaps can be named after the program's own spans."""
        from jax.profiler import ProfileData
        logdir = str(tmp_path / "prof")
        with telemetry.session(out_dir=str(tmp_path), export=False):
            with jax.profiler.trace(logdir):
                with telemetry.span("steam.test_span"):
                    jnp.arange(8.0).sum().block_until_ready()
        paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        assert paths
        names = {ev.name
                 for plane in ProfileData.from_file(paths[0]).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert "steam.test_span" in names

    def test_profile_wraps_jax_profiler(self, tmp_path):
        cfg = _cfg(batt=False)
        with telemetry.session(out_dir=str(tmp_path), export=False):
            try:
                out, logdir = telemetry.profile(
                    lambda: _run(cfg), logdir=str(tmp_path / "prof"))
            except Exception as e:  # pragma: no cover - profiler missing
                pytest.skip(f"jax.profiler.trace unavailable here: {e}")
        assert out.probes is None
        assert os.path.isdir(logdir)


# ---------------------------------------------------------------------------
# 2. probe bus: stage vs megakernel differential
# ---------------------------------------------------------------------------

PROBE_CASES = [
    # (cool, price, renew, stride, max_samples)
    (False, False, False, 1, 0),
    (True, False, False, 1, 0),
    (False, True, False, 3, 0),
    (True, True, True, 1, 0),
    (True, True, True, 4, 0),
    (False, True, True, 3, 10),   # ring wrap: keeps the LAST 10 samples
]


class TestProbeBus:
    @pytest.mark.parametrize("cool,price,renew,stride,cap", PROBE_CASES)
    def test_stage_and_megakernel_probes_match(self, cool, price, renew,
                                               stride, cap):
        cfg = _cfg(cool=cool, price=price, renew=renew,
                   probes=ProbeConfig(enabled=True, stride=stride,
                                      max_samples=cap))
        ps = _run(cfg).probes
        pm = _run(cfg.replace(backend="megakernel")).probes
        assert ps is not None and pm is not None
        k = telemetry.probe_capacity(S, cfg.probes)
        assert ps.step.shape == (k,)
        np.testing.assert_array_equal(np.asarray(ps.step),
                                      np.asarray(pm.step))
        for f in telemetry.PROBE_VALUE_FIELDS:
            np.testing.assert_allclose(
                np.asarray(getattr(ps, f)), np.asarray(getattr(pm, f)),
                rtol=1e-5, atol=1e-4, err_msg=f)

    def test_probes_match_collect_series_slices(self):
        """stride=1 probes are exactly the per-step flow series (same scan,
        same arithmetic — the probe stage just copies the settled ledger)."""
        cfg = _cfg(cool=True, price=True, renew=True, collect_series=True,
                   probes=ProbeConfig(enabled=True, stride=1))
        final, series = simulate(TASKS, HOSTS, CI, cfg, dyn=_dyn(cfg))
        p = summarize(final, cfg).probes
        flow = series["flow"]
        np.testing.assert_array_equal(np.asarray(p.step), np.arange(S))
        for f in ("it_kw", "cooling_kw", "pv_kw", "grid_import_kw",
                  "grid_export_kw", "curtailed_kw", "batt_charge_kw",
                  "batt_discharge_kw"):
            np.testing.assert_array_equal(np.asarray(getattr(p, f)),
                                          np.asarray(getattr(flow, f)),
                                          err_msg=f)
        np.testing.assert_array_equal(np.asarray(p.soc_kwh),
                                      np.asarray(series["battery_charge"]))

    def test_ring_wrap_keeps_last_samples(self):
        cfg = _cfg(probes=ProbeConfig(enabled=True, stride=2,
                                      max_samples=7))
        p = _run(cfg).probes
        total = -(-S // 2)                      # 48 strided samples
        # ring row j holds the last sample index == j (mod 7)
        want = [(j + ((total - 1 - j) // 7) * 7) * 2 for j in range(7)]
        np.testing.assert_array_equal(np.asarray(p.step), want)

    def test_pallas_megakernel_with_probes_falls_back_and_matches(self):
        """probes force the megakernel's facility phase onto the reference
        chain (the Pallas kernel emits only totals); results must still
        match the stage pipeline, and the totals must match the no-probe
        Pallas run."""
        cfg = _cfg(cool=True, price=True, backend="megakernel",
                   use_pallas=True,
                   probes=ProbeConfig(enabled=True, stride=1))
        probed = _run(cfg)
        plain = _run(cfg.replace(probes=ProbeConfig()))
        assert probed.probes is not None and plain.probes is None
        for f in probed._fields:
            if f == "probes":
                continue
            np.testing.assert_allclose(np.asarray(getattr(probed, f)),
                                       np.asarray(getattr(plain, f)),
                                       rtol=1e-5, atol=1e-4, err_msg=f)

    def test_resilience_channels_healthy_defaults(self):
        """The resilience channels exist unconditionally: with the loops
        open they read the identity values (no throttle, no derate, no
        clamp) on BOTH backends — dashboards never branch on config."""
        cfg = _cfg(probes=ProbeConfig(enabled=True, stride=1))
        for c in (cfg, cfg.replace(backend="megakernel")):
            p = _run(c).probes
            assert np.all(np.asarray(p.throttle_factor) == 1.0)
            assert np.all(np.asarray(p.chiller_derate) == 1.0)
            assert np.all(np.isinf(np.asarray(p.pdu_cap_kw)))

    def test_resilience_channels_match_across_backends(self):
        """Hazards forced high so every loop actually bites: the stage
        pipeline's in-scan samples and the megakernel's vectorized gather
        must report the same throttle/derate/clamp series."""
        cfg = _cfg(cool=True,
                   resilience=ResilienceConfig(
                       enabled=True, chiller_mtbf_h=8.0, chiller_repair_h=6.0,
                       pdu_mtbf_h=12.0, pdu_repair_h=4.0, pdu_cap_kw=5.0,
                       throttle_inlet_c=10.0, throttle_factor=0.5),
                   probes=ProbeConfig(enabled=True, stride=1))
        ps = _run(cfg).probes
        pm = _run(cfg.replace(backend="megakernel")).probes
        for f in ("throttle_factor", "chiller_derate", "pdu_cap_kw"):
            np.testing.assert_allclose(np.asarray(getattr(ps, f)),
                                       np.asarray(getattr(pm, f)),
                                       rtol=1e-6, err_msg=f)
        # the loops really closed: derate, throttle and clamp all engaged
        assert np.asarray(ps.chiller_derate).min() < 1.0
        assert np.asarray(ps.throttle_factor).min() < 1.0
        assert np.asarray(ps.pdu_cap_kw).min() == 5.0
        # throttle channel is the factor the step RAN under: step 0 is
        # always un-throttled (the trip applies on the NEXT tick)
        assert np.asarray(ps.throttle_factor)[0] == 1.0

    def test_queue_depth_is_sane(self):
        # oversubscribed on purpose: 8 two-core tasks, one 4-core host
        tasks = make_task_table(np.zeros(8), np.full(8, 2.0),
                                np.full(8, 2.0))
        hosts = make_host_table(1, 4)
        cfg = _cfg(probes=ProbeConfig(enabled=True, stride=1))
        final, _ = simulate(tasks, hosts, CI, cfg, dyn=_dyn(cfg))
        p = summarize(final, cfg).probes
        qd = np.asarray(p.queue_depth)
        assert (qd >= 0).all()
        assert qd.max() > 0       # only 2 of 8 tasks fit at once
        assert qd[-1] == 0.0      # horizon long enough to drain the queue

    def test_probes_ride_through_grid_vmap(self):
        cfg = _cfg(probes=ProbeConfig(enabled=True, stride=8))
        caps = np.array([2.0, 6.0], np.float32)
        res = sweep_grid(TASKS, HOSTS, cfg, [dyn_axis(batt_capacity_kwh=caps)],
                         CI)
        k = telemetry.probe_capacity(S, cfg.probes)
        assert res.probes.it_kw.shape == (2, k)
        # each grid cell's probes equal its standalone run
        for i, cap in enumerate(caps):
            ref = summarize(simulate(TASKS, HOSTS, CI, cfg,
                                     dyn={"batt_capacity_kwh": cap})[0],
                            cfg).probes
            for f in ref._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(res.probes, f))[i],
                    np.asarray(getattr(ref, f)), rtol=1e-6, atol=1e-6,
                    err_msg=f"{f} cell {i}")

    def test_window_peak_series_matches_scan_semantics(self):
        """The megakernel's vectorized running-peak reconstruction against a
        literal replay of pricing_step's close/reset recurrence."""
        rng = np.random.default_rng(0)
        grid = rng.uniform(0, 100, 50).astype(np.float32)
        w = 7
        got = np.asarray(telemetry.window_peak_series(jnp.asarray(grid), w))
        peak, want = 0.0, []
        for t, g in enumerate(grid):
            if t % w == 0 and t > 0:
                peak = 0.0
            peak = max(peak, g)
            want.append(peak)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# 3. recompile & cache-miss detector
# ---------------------------------------------------------------------------

def _cell_fn(salt):
    # a DISTINCT constant is folded into each cell's program, so every cell
    # re-traces and re-compiles — the bug class the detector must catch
    return jax.jit(lambda x: jnp.sum(x * salt))


class TestRecompileDetector:
    def test_warns_on_per_cell_recompilation(self):
        x = jnp.arange(64.0)
        with pytest.warns(UserWarning, match="recompiled in"):
            with telemetry.recompile_guard("sweep", allowed=1,
                                           policy="warn") as g:
                for i in range(4):
                    _cell_fn(1.0 + i)(x).block_until_ready()
                    g.tick()
        assert g.bursts >= 3

    def test_raises_under_raise_policy(self):
        x = jnp.arange(64.0)
        with pytest.raises(telemetry.RecompileError):
            with telemetry.recompile_guard("sweep", allowed=1,
                                           policy="raise") as g:
                for i in range(4):
                    _cell_fn(100.0 + i)(x).block_until_ready()
                    g.tick()

    def test_no_false_positive_on_cached_execution(self):
        x = jnp.arange(64.0)
        f = _cell_fn(-3.0)
        with telemetry.recompile_guard("steady", allowed=1,
                                       policy="raise") as g:
            for _ in range(5):
                f(x).block_until_ready()
                g.tick()
        assert g.bursts <= 1   # only the first call may compile

    def test_chunked_sweep_does_not_trip_the_guard(self, tmp_path, recwarn):
        """The grid chunk loop reuses ONE compiled program across equal-size
        chunks; the built-in guard must stay quiet."""
        cfg = _cfg()
        caps = np.array([2.0, 4.0, 8.0, 16.0], np.float32)
        with telemetry.session(out_dir=str(tmp_path), export=False):
            sweep_grid(TASKS, HOSTS, cfg, [dyn_axis(batt_capacity_kwh=caps)],
                       CI, chunk_size=2)
        assert not [w for w in recwarn.list
                    if "recompiled" in str(w.message)]

    def test_compile_watch_counts_fresh_compiles(self):
        x = jnp.arange(128.0)
        with telemetry.compile_watch() as w:
            _cell_fn(7.25)(x).block_until_ready()
        assert w.count >= 1
        assert w.seconds >= 0.0
        before = w.count
        _cell_fn(7.25)(x).block_until_ready()  # fresh wrapper, same program
        assert w.count >= before

    def test_compile_watch_times_tracing_and_lowering(self):
        x = jnp.arange(96.0)
        with telemetry.compile_watch() as w:
            _cell_fn(9.5)(x).block_until_ready()
        assert w.trace_seconds > 0.0 and w.lower_seconds > 0.0
        # the process totals hold at least what the block saw
        total = telemetry.CompileWatch(since_start=True)
        assert total.trace_seconds >= w.trace_seconds
        assert total.lower_seconds >= w.lower_seconds

    def test_a_trace_nested_in_a_later_one_counts_once(self, monkeypatch):
        """Tracing an outer jit traces its inner jits inside it, and each
        fires its own duration event when it ends: the clock counts the
        wall time they cover, not the sum of their durations."""
        now = iter([10.0, 12.0, 13.0, 20.0])
        monkeypatch.setattr(telemetry.time, "perf_counter",
                            lambda: next(now))
        clock = telemetry._WallClock()
        clock.add(1.0)    # inner: 9 .. 10
        clock.add(5.0)    # outer: 7 .. 12, holds the inner
        assert clock.seconds == 5.0
        clock.add(0.5)    # 12.5 .. 13, after it
        clock.add(7.5)    # 12.5 .. 20, overlaps the last one only
        assert clock.seconds == 5.0 + 7.5


# ---------------------------------------------------------------------------
# 4. run records
# ---------------------------------------------------------------------------

class TestRunRecords:
    def test_simulate_emits_record_with_time_split(self, tmp_path):
        cfg = _cfg()
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            _run(cfg)
            assert len(tel.records) == 1
            rec = tel.records[0]
        assert rec.kind == "simulate"
        assert rec.backend == "stage-pipeline"
        assert rec.n_steps == S
        assert rec.config_hash == telemetry.config_hash(cfg)
        assert rec.compile_time_s >= 0.0
        assert rec.execute_time_s >= 0.0
        assert rec.jax_backend == jax.default_backend()
        assert rec.device_count == jax.device_count()

    def test_grid_record_carries_chunk_plan_and_roundtrips(self, tmp_path):
        cfg = _cfg()
        caps = np.array([2.0, 4.0, 8.0, 16.0], np.float32)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            sweep_grid(TASKS, HOSTS, cfg, [dyn_axis(batt_capacity_kwh=caps)],
                       CI, chunk_size=2)
            recs = [r for r in tel.records if r.kind == "grid"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec.grid_shape == [4]
        assert rec.chunk["chunk_size"] == 2
        assert rec.chunk["n_chunks"] == 2
        assert rec.chunk["auto"] is False
        assert rec.chunk["predicted_bytes_per_lead"] > 0
        assert rec.chunk["actual_payload_bytes"] > 0
        # JSONL round-trip
        path = os.path.join(str(tmp_path), "run_records.jsonl")
        with open(path) as f:
            lines = f.readlines()
        parsed = [telemetry.RunRecord.from_json(l) for l in lines]
        assert any(dataclasses.asdict(p) == dataclasses.asdict(rec)
                   for p in parsed)

    def test_trace_dtype_recorded_per_axis(self, tmp_path):
        cfg = _cfg()
        traces = np.stack([CI, CI * 0.5]).astype(np.float32)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            sweep_grid(TASKS, HOSTS, cfg,
                       [trace_axis(traces, store="bf16")])
            rec = [r for r in tel.records if r.kind == "grid"][0]
        assert rec.trace_dtypes == {"ci_trace": "bfloat16"}

    def test_fleet_emits_record(self, tmp_path):
        cfg = _cfg(batt=False)
        fleet = FleetSpec(ci_traces=np.stack([CI, CI[::-1]]))
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            simulate_fleet(TASKS, HOSTS, cfg, fleet)
            recs = [r for r in tel.records if r.kind == "fleet"]
        assert len(recs) == 1
        assert recs[0].extra["n_regions"] == 2
        assert recs[0].extra["policy"] == "greedy"

    def test_pallas_interpret_lands_in_record(self, tmp_path):
        cfg = _cfg(backend="megakernel", use_pallas=True)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            _run(cfg)
            rec = tel.records[-1]
        # on the CPU test host the kernel must have resolved to interpret
        assert rec.pallas_interpret is True
        assert rec.use_pallas is True

    @pytest.mark.parametrize("platform,n_hosts,form", [
        ("cpu", 3, "one_hot"), ("cpu", 300, "segment_sum"),
        ("tpu", 300, "one_hot")])
    def test_per_host_sum_form_lands_in_record(self, tmp_path, monkeypatch,
                                               platform, n_hosts, form):
        """The form the per-host sums took is in the record: the CPU's
        matmul-or-scatter threshold, or the TPU's contraction at every host
        count.  (The TPU's placement kernel runs interpreted: this CPU
        runs no Mosaic.)"""
        from repro.core import device
        from repro.kernels import ops
        monkeypatch.setattr(device, "call_platform", lambda: platform)
        monkeypatch.setattr(ops, "resolved_interpret", lambda: True)
        cfg = _cfg(batt=False)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            simulate(TASKS, make_host_table(n_hosts, 4), CI, cfg)
            rec = tel.records[-1]
        assert rec.per_host_sum == form

    @pytest.mark.parametrize("platform,batched,form", [
        ("cpu", False, "while_loop"), ("tpu", False, "pallas"),
        ("tpu", True, "while_loop")])
    def test_first_fit_form_lands_in_record(self, tmp_path, monkeypatch,
                                            platform, batched, form):
        """The form the placement loop took is in the record: the kernel
        on the TPU (interpreted on this CPU), the `while_loop` elsewhere
        and wherever placement is batched by `vmap`."""
        from repro.core import device
        from repro.kernels import ops
        monkeypatch.setattr(device, "call_platform", lambda: platform)
        monkeypatch.setattr(ops, "resolved_interpret", lambda: True)
        cfg = _cfg(batt=False)

        def run(slots):
            final, _ = simulate(TASKS, HOSTS, CI, cfg,
                                dyn={"slots_per_step": slots})
            return final.metrics.first_fit_iters

        k = jnp.int32(cfg.scheduler.slots_per_step)
        with telemetry.session(out_dir=str(tmp_path), export=False) as tel:
            if batched:
                jax.vmap(run)(jnp.stack([k, k]))
            else:
                run(k)
            rec = tel.records[-1]
        assert rec.first_fit == form

    def test_first_fit_note_is_a_no_op_without_session(self):
        assert not telemetry.enabled()
        telemetry.note_first_fit("pallas")  # must not raise

    def test_per_host_sum_note_is_a_no_op_without_session(self):
        assert not telemetry.enabled()
        telemetry.note_per_host_sum("one_hot")  # must not raise
