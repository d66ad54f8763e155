"""Differential suite for the megakernel step executor + this PR's bugfixes.

The megakernel backend (core/engine.py, "Kernel backends") must be a pure
optimization: for every subsystem combination and battery policy it has to
reproduce the stage pipeline's results within float tolerance, and its
collect_series path must satisfy the same energy-flow conservation law the
ledger tier enforces on the stage scan.  The Pallas form of the fused
facility chain (kernels/fused_step.py) is additionally pinned against the
pure-jnp oracle (kernels/ref.py), tight for f32 trace storage and loose for
the quantized bf16/int8 stores.

Alongside the tentpole, the satellite bugfix regressions live here:
interpret-mode resolution (kernels/ops.resolved_interpret), the traced
`slots_per_step` masked-tail scheduler path, the scatter-free scheduler
helpers, and the dtype-aware auto-chunk estimate (core/grid.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BatteryConfig, CoolingConfig, FailureConfig,
                        PricingConfig, RenewableConfig, ResilienceConfig,
                        ScenarioGrid, SchedulerConfig,
                        ShiftingConfig, SimConfig, build_step_inputs,
                        device, dyn_axis, make_host_table, make_task_table,
                        simulate, summarize, sweep_grid, trace_axis,
                        weather_axis)
from repro.core.engine import BACKENDS, facility_totals_from_flows
from repro.core.scheduler import (_first_k_indices, _per_host_sum,
                                  per_host_sum_form)
from repro.kernels import ref as ref_mod
from repro.kernels.fused_step import fused_facility_totals
from repro.kernels.ops import resolved_interpret

S = 96
DT = 0.25

rng0 = np.random.default_rng(21)
N = 12
TASKS = make_task_table(np.sort(rng0.uniform(0.0, 8.0, N)),
                        rng0.uniform(0.5, 4.0, N),
                        rng0.integers(1, 3, N).astype(float))
HOSTS = make_host_table(3, 4)

COMBOS = [(cool, price, renew)
          for cool in (False, True)
          for price in (False, True)
          for renew in (False, True)]


def _traces(seed: int):
    rng = np.random.default_rng(seed)
    t = np.arange(S) * DT
    ci = (rng.uniform(50, 600)
          * (1 + rng.uniform(0, 0.8) * np.sin(2 * np.pi * t / 24
                                              + rng.uniform(0, 6)))
          + rng.normal(0, 10, S)).clip(5.0).astype(np.float32)
    price = (rng.uniform(0.05, 0.2)
             * (1 + rng.uniform(0, 0.9) * np.sin(2 * np.pi * t / 24
                                                 + rng.uniform(0, 6)))
             + rng.exponential(0.01, S)).clip(0.005).astype(np.float32)
    wb = (rng.uniform(5, 25)
          + 6.0 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    day = np.clip(np.sin(2 * np.pi * (t - 6.0) / 24.0), 0.0, 1.0)
    cf = (day * rng.uniform(0.3, 0.9)).astype(np.float32)
    return ci, price, wb, cf


CI, PRICE, WB, CF = _traces(7)
DYN = {"price_trace": jnp.asarray(PRICE), "wet_bulb_trace": jnp.asarray(WB),
       "pv_cf_trace": jnp.asarray(CF)}


def _cfg(cool, price, renew, policy="carbon", batt=True, export=True,
         **kw):
    base = dict(
        n_steps=S, collect_series=False,
        cooling=CoolingConfig(enabled=cool, heat_reuse_fraction=0.3),
        pricing=PricingConfig(enabled=price, billing_window_h=12.0),
        renewables=RenewableConfig(enabled=renew, export_allowed=export,
                                   pv_capacity_kw=25.0),
        battery=BatteryConfig(enabled=batt, capacity_kwh=6.0, policy=policy,
                              price_window_h=24.0))
    base.update(kw)
    return SimConfig(**base)


def _dyn(cfg):
    """The exogenous traces each enabled subsystem consumes (the engine
    rejects traces whose subsystem is off)."""
    d = {}
    if cfg.pricing.enabled or cfg.battery.policy != "carbon":
        d["price_trace"] = DYN["price_trace"]
    if cfg.cooling.enabled:
        d["wet_bulb_trace"] = DYN["wet_bulb_trace"]
    if cfg.renewables.enabled:
        d["pv_cf_trace"] = DYN["pv_cf_trace"]
    return d


def _run(cfg):
    final, _ = simulate(TASKS, HOSTS, CI, cfg, dyn=_dyn(cfg))
    return summarize(final, cfg)


def _assert_results_close(a, b, rtol=1e-5, atol=1e-4):
    for k in a._fields:
        if getattr(a, k) is None and getattr(b, k) is None:
            continue  # SimResult.probes is None unless cfg.probes.enabled
        va, vb = np.asarray(getattr(a, k)), np.asarray(getattr(b, k))
        np.testing.assert_allclose(va.astype(np.float64),
                                   vb.astype(np.float64), rtol=rtol,
                                   atol=atol, err_msg=f"field {k}")


# ---------------------------------------------------------------------------
# tentpole: megakernel backend == stage pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cool,price,renew", COMBOS)
def test_megakernel_matches_stage_pipeline(cool, price, renew):
    policies = ("carbon", "price", "blended") if price else ("carbon",)
    for policy in policies:
        for batt in (False, True):
            cfg = _cfg(cool, price, renew, policy=policy, batt=batt)
            ref = _run(cfg.replace(backend="stage-pipeline"))
            got = _run(cfg.replace(backend="megakernel"))
            _assert_results_close(got, ref)


def test_megakernel_series_and_conservation():
    """collect_series on the fused path: the ledger law must hold and the
    series must match the stage pipeline's EnergyFlow."""
    cfg = _cfg(True, True, True, policy="blended",
               collect_series=True)
    for backend in BACKENDS:
        final, ys = simulate(TASKS, HOSTS, CI, cfg.replace(backend=backend),
                             dyn=_dyn(cfg))
        flow = ys["flow"]
        f = {k: np.asarray(getattr(flow, k)) for k in flow._fields}
        lhs = f["grid_import_kw"] + f["pv_kw"] + f["batt_discharge_kw"]
        rhs = (f["it_kw"] + f["cooling_kw"] + f["batt_charge_kw"]
               + f["grid_export_kw"] + f["curtailed_kw"])
        scale = max(float(np.abs(rhs).max()), 1.0)
        np.testing.assert_allclose(lhs, rhs, rtol=1e-5, atol=1e-4 * scale,
                                   err_msg=f"ledger violated [{backend}]")
        if backend == "stage-pipeline":
            ref_flow = f
        else:
            for k, v in f.items():
                np.testing.assert_allclose(
                    v, ref_flow[k], rtol=1e-4, atol=1e-3 * scale,
                    err_msg=f"series {k} diverges from stage pipeline")


def test_megakernel_matches_stage_pipeline_typed_workload():
    """Typed-workload differential: all three job classes, priority
    scheduling, shifting with stop/resume and the interactive bypass — the
    demand scan is shared code, but the new TaskTable columns must thread
    through the fused facility chain unchanged."""
    rng = np.random.default_rng(33)
    n = 18
    tasks = make_task_table(np.sort(rng.uniform(0.0, 8.0, n)),
                            rng.uniform(0.5, 4.0, n),
                            rng.integers(1, 3, n).astype(float),
                            job_class=rng.integers(0, 3, n).astype(np.int32),
                            sla_grace=rng.choice([-1.0, 0.25], n))
    cfg = _cfg(True, True, True, policy="blended",
               shifting=ShiftingConfig(enabled=True, stop_running=True,
                                       max_delay_h=12.0),
               scheduler=SchedulerConfig(priority_levels=3))
    results = {}
    for backend in BACKENDS:
        final, _ = simulate(tasks, HOSTS, CI, cfg.replace(backend=backend),
                            dyn=_dyn(cfg))
        results[backend] = summarize(final, cfg)
    _assert_results_close(results["megakernel"], results["stage-pipeline"])
    # the typed run actually exercised every class
    assert np.all(np.asarray(results["megakernel"].class_n_started) > 0)


@pytest.mark.parametrize("cool,price,renew", COMBOS)
def test_megakernel_matches_stage_pipeline_resilience(cool, price, renew):
    """Closed-loop resilience differential: with facility failures, PDU
    caps and thermal throttling live, the megakernel's demand scan carries
    the throttle recurrence itself — it must still reproduce the stage
    pipeline across the technique matrix."""
    res = ResilienceConfig(enabled=True, chiller_mtbf_h=15.0,
                           chiller_repair_h=3.0, pdu_mtbf_h=25.0,
                           pdu_repair_h=2.0, pdu_cap_kw=3.0,
                           throttle_inlet_c=24.0, heat_hazard_mult=2.0)
    cfg = _cfg(cool, price, renew, policy="blended" if price else "carbon",
               resilience=res, seed=42,
               failures=FailureConfig(enabled=True, mtbf_h=30.0))
    ref = _run(cfg.replace(backend="stage-pipeline"))
    got = _run(cfg.replace(backend="megakernel"))
    _assert_results_close(got, ref)
    if cool:
        # the wet-bulb trace peaks past the trip point whenever cooling is
        # on, so the throttle loop genuinely engaged in this differential
        assert float(ref.throttled_h) > 0.0


def test_backend_validation():
    with pytest.raises(ValueError, match="unknown backend"):
        simulate(TASKS, HOSTS, CI, _cfg(False, False, False,
                                        backend="warpdrive"))
    with pytest.raises(ValueError, match="stage-pipeline"):
        simulate(TASKS, HOSTS, CI,
                 _cfg(False, False, False, backend="megakernel"),
                 stages=[])


# ---------------------------------------------------------------------------
# Pallas fused facility kernel vs the pure-jnp oracle
# ---------------------------------------------------------------------------

def _fused_inputs(cfg):
    inputs = build_step_inputs(CI, cfg, _dyn(cfg))
    rng = np.random.default_rng(3)
    it_kw = jnp.asarray(rng.uniform(20.0, 80.0, S), jnp.float32)
    return it_kw, inputs


def _oracle_totals(it_kw, inputs, cfg):
    flows = ref_mod.fused_facility_chain(
        it_kw, inputs.ci, inputs.wet_bulb_c, inputs.price, inputs.price_lo,
        inputs.price_hi, inputs.pv_cf, inputs.batt_threshold,
        inputs.ci_rising, cfg.dt_h, cfg)
    return facility_totals_from_flows(flows, inputs, cfg)


@pytest.mark.parametrize("cool,price,renew", COMBOS)
def test_fused_kernel_matches_oracle_f32(cool, price, renew):
    policy = "blended" if price else "carbon"
    cfg = _cfg(cool, price, renew, policy=policy)
    it_kw, inputs = _fused_inputs(cfg)
    want = _oracle_totals(it_kw, inputs, cfg)
    got = fused_facility_totals(
        it_kw, inputs.ci, inputs.wet_bulb_c, inputs.price, inputs.price_lo,
        inputs.price_hi, inputs.pv_cf, inputs.batt_threshold,
        inputs.ci_rising, cfg, trace_store="f32", interpret=True)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(
            np.float64(got[k]), np.float64(want[k]), rtol=1e-4, atol=1e-3,
            err_msg=f"fused-kernel total {k}")


@pytest.mark.parametrize("store,rel", [("bf16", 5e-3), ("int8", 1e-2)])
def test_fused_kernel_quantized_stores(store, rel):
    """bf16/int8 trace storage: totals within the store's error envelope.

    Battery stays OFF: threshold-crossing dispatch decisions can flip under
    quantized carbon intensity, which is a (documented) behavioural change,
    not a numeric error — the envelope below is only meaningful for the
    decision-free energy accounting.
    """
    cfg = _cfg(True, True, True, batt=False)
    it_kw, inputs = _fused_inputs(cfg)
    base = _oracle_totals(it_kw, inputs, cfg)
    got = fused_facility_totals(
        it_kw, inputs.ci, inputs.wet_bulb_c, inputs.price, inputs.price_lo,
        inputs.price_hi, inputs.pv_cf, inputs.batt_threshold,
        inputs.ci_rising, cfg, trace_store=store, interpret=True)
    for k in ("grid_energy", "it_energy", "dc_energy", "op_carbon",
              "cooling_energy", "pv_energy", "energy_cost"):
        ref_v = float(base[k])
        err = abs(float(got[k]) - ref_v) / max(abs(ref_v), 1e-6)
        assert err <= rel, f"{store} {k}: rel err {err:.2e} > {rel}"


# ---------------------------------------------------------------------------
# satellite: interpret-mode dispatch (kernels/ops.resolved_interpret)
# ---------------------------------------------------------------------------

def test_resolved_interpret_follows_backend():
    assert resolved_interpret() is (jax.default_backend() == "cpu")


@pytest.mark.parametrize("pin", [None, "cpu", "cpu-device"])
@pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
def test_resolved_interpret_follows_backend_alone(monkeypatch, backend, pin):
    """Interpret mode is exactly "the platform the call runs on is CPU":
    the default backend, unless `jax.default_device` pins a device (as a
    run that checks the chip against the host CPU does)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if pin is None:
        assert resolved_interpret() is (backend == "cpu")
        return
    device = "cpu" if pin == "cpu" else jax.devices("cpu")[0]
    with jax.default_device(device):
        assert resolved_interpret() is True


# ---------------------------------------------------------------------------
# satellite: traced slots_per_step (masked fori_loop tail)
# ---------------------------------------------------------------------------

def test_slots_per_step_dyn_axis_matches_static():
    """Sweeping dyn_axis(slots_per_step=...) inside ONE compiled program
    must equal recompiling with each static bound."""
    slots = np.array([1, 2, 4, 8], np.int32)
    cfg = _cfg(False, False, False, batt=False,
               scheduler=SchedulerConfig(slots_per_step=int(slots.max())))
    swept = sweep_grid(TASKS, HOSTS, cfg, [dyn_axis(slots_per_step=slots)],
                       CI)
    for i, k in enumerate(slots):
        static = _run(cfg.replace(
            scheduler=SchedulerConfig(slots_per_step=int(k))))
        for field in static._fields:
            if getattr(static, field) is None:
                continue  # probes: off by default
            np.testing.assert_allclose(
                np.asarray(getattr(swept, field))[i],
                np.asarray(getattr(static, field)), rtol=1e-6, atol=1e-6,
                err_msg=f"slots={k} field {field}")


# ---------------------------------------------------------------------------
# satellite: scatter-free scheduler helpers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_cols", [1, 2])
@pytest.mark.parametrize("h", [1, 3, 17, 256, 257, 277, 972, 1534])
@pytest.mark.parametrize("platform", ["host", "tpu"])
def test_per_host_sum_matches_segment_sum(monkeypatch, platform, h, n_cols):
    """The per-host sum against segment_sum in the form this host's
    platform takes and in the TPU's (one one-hot contraction at every host
    count).  Integer columns equal it bitwise, float columns to
    rounding."""
    if platform == "tpu":
        monkeypatch.setattr(device, "call_platform", lambda: "tpu")
        assert per_host_sum_form(h) == "one_hot"
    elif jax.default_backend() == "cpu":  # the host CPU's threshold rule
        assert per_host_sum_form(h) == ("one_hot" if h <= 256
                                        else "segment_sum")
    t = max(257, 3 * h)
    rng = np.random.default_rng(5 + h)
    seg = jnp.asarray(rng.integers(0, h, t), jnp.int32)
    ints = rng.integers(0, 7, t).astype(np.float32)
    floats = rng.uniform(0, 1, t).astype(np.float32)
    stacks = ([ints[:, None], floats[:, None]] if n_cols == 1
              else [np.stack([ints, floats], axis=1)])
    got = np.concatenate(
        [np.asarray(_per_host_sum(jnp.asarray(v), seg, h)) for v in stacks],
        axis=1)
    assert got.shape == (h, 2)
    np.testing.assert_array_equal(
        got[:, 0], np.asarray(jax.ops.segment_sum(jnp.asarray(ints), seg, h)))
    np.testing.assert_allclose(
        got[:, 1],
        np.asarray(jax.ops.segment_sum(jnp.asarray(floats), seg, h)),
        rtol=1e-6)


def test_tpu_per_host_sums_simulate_like_host_form(monkeypatch):
    """Two days of the SURF deployment (277 hosts, above the host CPU's
    matmul threshold): the TPU's contraction, forced here, against the
    host's segment_sum.  Placement reads integer sums, so every task
    starts, lands and finishes identically; energy, carbon and cost read
    the float utilization sums and move by rounding only.  The TPU's
    placement kernel runs too, interpreted: this CPU runs no Mosaic."""
    from repro.kernels import ops as kernel_ops
    from repro.workloads.synthetic import make_workload
    tasks, hosts, _, meta = make_workload("surf", seed=3, horizon_days=2)
    assert meta["n_hosts"] == 277
    n = 2 * 96
    t = np.arange(n) * DT
    ci = (300 + 150 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    price = (0.1 + 0.05 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    wb = (15 + 6 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    cfg = SimConfig(n_steps=n, backend="megakernel", use_pallas=False,
                    cooling=CoolingConfig(enabled=True),
                    pricing=PricingConfig(enabled=True))
    dyn = {"price_trace": jnp.asarray(price),
           "wet_bulb_trace": jnp.asarray(wb)}

    def run():
        final, _ = simulate(tasks, hosts, ci, cfg, dyn=dict(dyn))
        return final.tasks, summarize(final, cfg)

    assert per_host_sum_form(277) == "segment_sum"
    host_tasks, host_res = run()
    monkeypatch.setattr(device, "call_platform", lambda: "tpu")
    monkeypatch.setattr(kernel_ops, "resolved_interpret", lambda: True)
    assert per_host_sum_form(277) == "one_hot"
    tpu_tasks, tpu_res = run()
    assert float(host_res.n_started) > 0
    for field in ("status", "host", "first_start", "finish"):
        np.testing.assert_array_equal(np.asarray(getattr(tpu_tasks, field)),
                                      np.asarray(getattr(host_tasks, field)),
                                      err_msg=field)
    for field in ("it_energy_kwh", "dc_energy_kwh", "total_carbon_kg",
                  "total_cost"):
        np.testing.assert_allclose(float(getattr(tpu_res, field)),
                                   float(getattr(host_res, field)),
                                   rtol=1e-6, err_msg=field)


def test_first_k_indices_matches_reference():
    rng = np.random.default_rng(6)
    for n, k in ((1, 1), (33, 4), (128, 16), (64, 64)):
        for density in (0.0, 0.1, 0.5, 1.0):
            mask = rng.uniform(size=n) < density
            want = np.full(k, -1, np.int32)
            hits = np.flatnonzero(mask)[:k]
            want[: hits.size] = hits
            got = np.asarray(_first_k_indices(jnp.asarray(mask), k))
            np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# satellite: dtype-aware auto-chunk sizing (core/grid.py)
# ---------------------------------------------------------------------------

def test_auto_chunk_size_sees_store_dtypes():
    """The memory estimate must price a quantized axis at its actual bytes:
    int8 storage is 4x lighter than f32, so under the same budget the int8
    grid gets chunks at least as large — and strictly larger for SOME
    budget (the old estimate priced every store identically)."""
    n_steps, r = 2048, 64
    ci = np.tile(_traces(9)[0], (r, n_steps // S + 1))[:, :n_steps]
    wb = np.tile(_traces(10)[2], (r, n_steps // S + 1))[:, :n_steps]
    cfg = _cfg(True, False, False, batt=False, n_steps=n_steps)

    def chunk(store, budget):
        grid = ScenarioGrid([trace_axis(ci, store=store),
                             weather_axis(wb[:2], store=store)])
        return grid._auto_chunk_size(TASKS, HOSTS, cfg, budget)

    budgets = [2.0 ** k for k in range(16, 30)]
    assert all(chunk("int8", b) >= chunk("f32", b) for b in budgets)
    assert any(chunk("int8", b) > chunk("f32", b) for b in budgets)
    # a generous budget returns the full leading length (legacy unchunked)
    assert chunk("f32", 2.0 ** 40) == r
