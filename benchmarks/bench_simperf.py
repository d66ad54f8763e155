"""Paper §VIII: simulator performance — simulated datacenter-time per
wall-second.

The paper: 2,787 years simulated in 60 compute-hours (single-threaded Java,
~0.0127 sim-years/core-second).  Here one jitted+vmapped tensor program
sweeps regions simultaneously; we report sim-years/second for BOTH step
executors (core/engine.py "Kernel backends"):

  stage-pipeline : the composable per-step stage scan (the baseline)
  megakernel     : demand scan + fused facility chain (vectorized over the
                   whole horizon; ONE time-blocked Pallas kernel under
                   use_pallas, kernels/fused_step.py)

Three configurations per backend: `bare` (no facility techniques — the
metric the seed's results/bench/simperf.json reported, so the speed
trajectory is comparable across PRs), `techniques` (cooling + pricing +
renewables + battery, the composition the paper sweeps and the part the
megakernel fuses) and `typed` (priority-aware scheduling + shifting with a
35% interactive fraction — the demand-side workload subsystem).  For the
untyped variants the demand scan is trace-independent, so XLA hoists it
out of the vmap batch (computed once, not x N); `typed` turns on shifting,
whose gate reads each lane's carbon trace, making the demand scan
per-lane — the structurally irreducible cost the single-pass scheduler,
presorted task table and bucket-decomposed windowed quantiles minimize
(root-cause analysis + key construction: benchmarks/PERFORMANCE.md).  The
fail-able claims below are the speed TRAJECTORY: vmap64 bare and vmap16
typed throughput must each stay >= 2x their seed baselines.

A weak-scaling mode rides along: the shard_map executor
(core/grid.py `ScenarioGrid.run_shard_map`) places one leading-axis chunk
of `WEAK_CELLS_PER_DEVICE` cells per device — cells grow with the device
count, so FLAT per-device sim-yr/s across device counts is the pass
condition.  Rows carry `per_device`, the device memory watermark
(`peak_bytes_per_device`, None where the backend exposes no allocator
stats — CPU) and the chunk plan's `predicted_bytes_per_lead` side by side.

Besides results/bench/simperf.json this module publishes BENCH_simperf.json
at the repo root: the headline numbers (single / vmapN / per-device /
weak-scaling, both backends, all configs) that README-level claims and the
CI bench-smoke gate point at; run.py appends the headline summary to
BENCH_simperf.history.jsonl per invocation.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from repro.core import (BatteryConfig, CoolingConfig, PricingConfig,
                        RenewableConfig, SchedulerConfig, ShiftingConfig,
                        simulate, summarize, sweep_grid, trace_axis,
                        telemetry)
from repro.core.grid import ScenarioGrid
from repro.kernels.ops import resolved_interpret
from .common import DT_H, pct, regions, save_rows, setup, time_split

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_FILE = os.path.join(REPO_ROOT, "BENCH_simperf.json")

BACKENDS = ("stage-pipeline", "megakernel")

# Seed-repo baselines (results/bench/simperf.json before this PR), the
# reference points for the speed-trajectory claim in check().
SEED_VMAP64_YEARS_PER_S = 5.6
SEED_PALLAS_YEARS_PER_S = 0.089
# The typed variant's vmap16 rate BEFORE the single-pass scheduler /
# presorted-table / windowed-quantile rework (the ~20x batching collapse
# this campaign removed; see benchmarks/PERFORMANCE.md).  check() gates the
# typed vmap16 rate at >= 2x this value; the weak-scaling mode gates the
# PER-DEVICE typed rate at the same bar even under --smoke (a RuntimeError
# inside run() surfaces as a SUITE ERROR, which does fail CI bench-smoke).
SEED_TYPED_VMAP16_YEARS_PER_S = 0.33
WEAK_TYPED_GATE_YEARS_PER_S = 2.0 * SEED_TYPED_VMAP16_YEARS_PER_S

# Weak-scaling mode: cells grow with the device count so the per-device
# block (and working set) stays constant — flat per-device sim-yr/s over
# devices is the pass condition, falling per-device rate is lost scaling.
WEAK_CELLS_PER_DEVICE = 8


def _time(fn, *args, reps=3):
    """Compile-then-steady timing: `steady_s` drives the sim-years/s rate
    (same semantics as before the split); the compile side rides along on
    each row so regressions in either show up separately."""
    return time_split(fn, *args, reps=reps)


def _technique_cfg(cfg):
    """The composed-techniques configuration (cooling + pricing + PV +
    battery): the facility chain the megakernel fuses."""
    return cfg.replace(
        cooling=CoolingConfig(enabled=True, heat_reuse_fraction=0.3),
        pricing=PricingConfig(enabled=True, billing_window_h=24.0),
        renewables=RenewableConfig(enabled=True, pv_capacity_kw=40.0),
        battery=BatteryConfig(enabled=True, capacity_kwh=100.0,
                              policy="carbon"))


def _typed_cfg(cfg):
    """The typed-workload configuration: priority-aware scheduling +
    shifting with the interactive bypass; the `interactive_frac` dyn key
    re-types a share of tasks inside the program.  Benchmarks the
    per-priority-level scheduler passes and the per-class metric matmuls."""
    return cfg.replace(
        shifting=ShiftingConfig(enabled=True, max_delay_h=24.0),
        scheduler=SchedulerConfig(priority_levels=3))


def _shared_traces(n_steps: int):
    """Deterministic weather/price/pv series shared across the region sweep
    (the swept axis is the carbon trace)."""
    t = np.arange(n_steps) * DT_H
    price = (0.1 * (1 + 0.5 * np.sin(2 * np.pi * t / 24))).astype(np.float32)
    wb = (14.0 + 6.0 * np.sin(2 * np.pi * t / 24)).astype(np.float32)
    cf = np.clip(np.sin(2 * np.pi * (t - 6.0) / 24.0), 0.0, 1.0).astype(
        np.float32)
    return {"price_trace": price, "wet_bulb_trace": wb, "pv_cf_trace": cf}


def _weak_scaling_rows(tasks, hosts, cfg, sim_years):
    """Weak-scaling mode: the shard_map executor (core/grid.py) places one
    leading-axis chunk of WEAK_CELLS_PER_DEVICE cells per device; rows
    report per-device sim-yr/s next to the device memory watermark and the
    chunk plan's predicted bytes.  At one device the executor must be
    bitwise-equal to the chunked path (acceptance criterion — checked here
    on every run, so CI bench-smoke pins it too); the typed per-device rate
    is gated at WEAK_TYPED_GATE_YEARS_PER_S via RuntimeError (--smoke skips
    check(), so the gate lives inside run())."""
    ndev = jax.device_count()
    mesh = jax.make_mesh((ndev,), ("data",))
    cells = WEAK_CELLS_PER_DEVICE * ndev
    traces = regions(cells, cfg.n_steps)
    rows, summary = [], {"device_count": ndev, "cells": cells}
    for variant, vcfg, dyn in [
            ("bare", cfg, {}),
            ("typed", _typed_cfg(cfg),
             {"interactive_frac": np.float32(0.35)})]:
        grid = ScenarioGrid([trace_axis(traces)], base_dyn=dict(dyn))
        # donate=False: the SAME payload arrays are re-submitted each
        # timing rep (donation would invalidate them after the first call)
        call = grid.shard_map_callable(tasks, hosts, vcfg, mesh=mesh,
                                       donate=False)
        payloads = grid.payloads()
        if ndev == 1:
            # acceptance: shard_map executor == single-device chunked path,
            # bitwise — any drift here means the executors diverged
            ref = sweep_grid(tasks, hosts, vcfg, [trace_axis(traces)],
                             dyn=dict(dyn))
            got = call(*payloads)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise RuntimeError(
                        f"weak-scaling executor diverged from the chunked "
                        f"path at device_count=1 ({variant} variant): "
                        f"{np.asarray(a).ravel()[:3]} vs "
                        f"{np.asarray(b).ravel()[:3]}")
        tm = _time(call, *payloads)
        t_w = tm["steady_s"]
        per_dev = sim_years * cells / t_w / ndev
        peak = telemetry.peak_bytes_per_device()
        row = {"bench": "simperf", "backend": "stage-pipeline",
               "variant": variant, "mode": "weak_scaling",
               "metric": f"sim_years_per_s_weak[{variant},ndev={ndev}]",
               "value": pct(sim_years * cells / t_w),
               "per_device": pct(per_dev),
               "device_count": ndev, "cells": cells,
               "cells_per_device": WEAK_CELLS_PER_DEVICE,
               "wall_s": pct(t_w), "compile_s": pct(tm["compile_s"]),
               "first_call_s": pct(tm["first_call_s"]),
               "peak_bytes_per_device": peak,
               "predicted_bytes_per_lead": pct(
                   grid._per_lead_bytes(tasks, hosts, vcfg))}
        rows.append(row)
        summary[f"{variant}_per_device_years_per_s"] = pct(per_dev)
        if variant == "typed" and per_dev < WEAK_TYPED_GATE_YEARS_PER_S:
            raise RuntimeError(
                f"weak-scaling typed throughput regressed: {per_dev:.3f} "
                f"sim-yr/s per device < gated baseline "
                f"{WEAK_TYPED_GATE_YEARS_PER_S} (2x the pre-campaign "
                f"typed rate {SEED_TYPED_VMAP16_YEARS_PER_S})")
    summary["peak_bytes_per_device"] = rows[-1]["peak_bytes_per_device"]
    summary["typed_gate_years_per_s"] = WEAK_TYPED_GATE_YEARS_PER_S
    return rows, summary


def run(quick: bool = True):
    from . import common
    rows = []
    tasks, hosts, meta, cfg = setup("surf", quick, days=14.0, tasks_cap=1024)
    sim_years = cfg.n_steps * cfg.dt_h / 8766.0
    task_steps = float(meta["n_tasks"]) * cfg.n_steps   # fairness unit
    ndev = jax.device_count()
    # log the kernel-dispatch mode ONCE, not per pallas row: on CPU the
    # fused kernels run under the Pallas interpreter, so their wall-time is
    # an API/correctness signal rather than a perf claim
    interp = resolved_interpret()
    print(f"simperf: pallas interpret={interp} "
          f"(backend={jax.default_backend()}, devices={ndev})", flush=True)

    trace = regions(1, cfg.n_steps)[0]
    vmap_sizes = (16,) if common.SMOKE else (16, 64)
    variants = [("bare", cfg, {}),
                ("techniques", _technique_cfg(cfg),
                 _shared_traces(cfg.n_steps)),
                ("typed", _typed_cfg(cfg),
                 {"interactive_frac": np.float32(0.35)})]
    for variant, vcfg, dyn in variants:
        for backend in BACKENDS:
            cfg_b = vcfg.replace(backend=backend)
            jit_one = jax.jit(lambda tr, c=cfg_b, d=dyn: summarize(
                simulate(tasks, hosts, tr, c, dyn=dict(d))[0], c))
            tm = _time(jit_one, trace)
            t_one = tm["steady_s"]
            rows.append({"bench": "simperf", "backend": backend,
                         "variant": variant,
                         "metric": f"sim_years_per_s_single"
                                   f"[{backend},{variant}]",
                         "value": pct(sim_years / t_one),
                         "wall_s": pct(t_one),
                         "compile_s": pct(tm["compile_s"]),
                         "first_call_s": pct(tm["first_call_s"]),
                         "per_device": pct(sim_years / t_one / ndev),
                         "task_steps_per_s": pct(task_steps / t_one),
                         "paper_java_years_per_core_s": 0.0127})

            for r in vmap_sizes:
                traces = regions(r, cfg.n_steps)
                # pre-jit ONCE: sweep(jit=True) builds a fresh jit wrapper
                # per call, which would time compilation, not the sweep
                fn = jax.jit(lambda tr, c=cfg_b, d=dyn: sweep_grid(
                    tasks, hosts, c, [trace_axis(tr)], dyn=dict(d),
                    jit=False))
                tm = _time(fn, traces)
                t_vmap = tm["steady_s"]
                rows.append({"bench": "simperf", "backend": backend,
                             "variant": variant,
                             "metric": f"sim_years_per_s_vmap{r}"
                                       f"[{backend},{variant}]",
                             "value": pct(sim_years * r / t_vmap),
                             "per_device": pct(sim_years * r / t_vmap / ndev),
                             "task_steps_per_s": pct(task_steps * r / t_vmap),
                             "wall_s": pct(t_vmap),
                             "compile_s": pct(tm["compile_s"]),
                             "first_call_s": pct(tm["first_call_s"])})

    # Pallas rows: stage-pipeline dispatches its fused power/carbon op every
    # scan step; the megakernel dispatches ONE time-blocked facility kernel
    # (kernels/fused_step.py) — on CPU both run interpreted
    for backend in BACKENDS:
        cfg_p = _technique_cfg(cfg).replace(backend=backend, use_pallas=True)
        dyn = _shared_traces(cfg.n_steps)
        jit_p = jax.jit(lambda tr, c=cfg_p, d=dyn: summarize(
            simulate(tasks, hosts, tr, c, dyn=dict(d))[0], c))
        tm = _time(jit_p, trace, reps=1)
        t_pal = tm["steady_s"]
        rows.append({"bench": "simperf", "backend": backend,
                     "variant": "techniques", "interpret": bool(interp),
                     "metric": f"sim_years_per_s_pallas[{backend}]",
                     "value": pct(sim_years / t_pal), "wall_s": pct(t_pal),
                     "compile_s": pct(tm["compile_s"]),
                     "first_call_s": pct(tm["first_call_s"])})

    weak_rows, weak_summary = _weak_scaling_rows(tasks, hosts, cfg,
                                                 sim_years)
    rows += weak_rows

    save_rows("simperf", rows)
    with open(BENCH_FILE, "w") as f:
        json.dump({"bench": "simperf", "smoke": bool(common.SMOKE),
                   "backend": jax.default_backend(),
                   "device_count": ndev, "pallas_interpret": bool(interp),
                   "compile_s_total": pct(sum(r.get("compile_s", 0.0)
                                              for r in rows)),
                   "steady_s_total": pct(sum(r.get("wall_s", 0.0)
                                             for r in rows)),
                   "sim_years_per_run": pct(sim_years),
                   "seed_baseline": {
                       "vmap64": SEED_VMAP64_YEARS_PER_S,
                       "pallas": SEED_PALLAS_YEARS_PER_S,
                       "typed_vmap16": SEED_TYPED_VMAP16_YEARS_PER_S},
                   "weak_scaling": weak_summary,
                   "rows": rows}, f, indent=1, default=float)
    return rows


def _get(rows, metric):
    return next(r for r in rows if r["metric"] == metric)


def check(rows) -> list[str]:
    one = _get(rows, "sim_years_per_s_single[stage-pipeline,bare]")
    vm = _get(rows, "sim_years_per_s_vmap64[stage-pipeline,bare]")
    mk_vm = _get(rows, "sim_years_per_s_vmap64[megakernel,techniques]")
    st_vm = _get(rows, "sim_years_per_s_vmap64[stage-pipeline,techniques]")
    mk_pal = _get(rows, "sim_years_per_s_pallas[megakernel]")
    ty_vm = _get(rows, "sim_years_per_s_vmap16[stage-pipeline,typed]")
    te_vm = _get(rows, "sim_years_per_s_vmap16[stage-pipeline,techniques]")
    weak = next(r for r in rows if r.get("mode") == "weak_scaling"
                and r["variant"] == "typed")
    speedup = vm["value"] / max(one["value"], 1e-9)
    vs_paper = one["value"] / 0.0127
    vs_seed = vm["value"] / SEED_VMAP64_YEARS_PER_S
    mk_gain = mk_vm["value"] / max(st_vm["value"], 1e-9)
    pal_vs_seed = mk_pal["value"] / SEED_PALLAS_YEARS_PER_S
    ty_vs_seed = ty_vm["value"] / SEED_TYPED_VMAP16_YEARS_PER_S
    ty_gap = te_vm["value"] / max(ty_vm["value"], 1e-9)
    seed_verdict = ("OK" if vs_seed >= 2.0
                    else "FAIL: hot loop regressed below 2x the seed")
    mk_verdict = ("OK" if mk_gain >= 1.0
                  else "WEAK: shared demand-scan floor dominates on this host")
    ty_verdict = ("OK" if ty_vs_seed >= 2.0
                  else "FAIL: typed demand scan regressed below 2x the "
                       "pre-campaign rate")
    weak_verdict = ("OK" if weak["per_device"] >= WEAK_TYPED_GATE_YEARS_PER_S
                    else "FAIL: weak-scaling typed per-device rate below "
                         "the gated baseline")
    return [
        f"simperf: single-sim {one['value']} sim-years/s = {vs_paper:.0f}x "
        f"the paper's per-core Java rate",
        f"simperf: vmap(64) batches to {vm['value']} sim-years/s "
        f"({speedup:.1f}x single) ({'OK' if speedup > 4 else 'WEAK'})",
        f"simperf: vmap(64) is {vs_seed:.1f}x the seed-repo baseline "
        f"({SEED_VMAP64_YEARS_PER_S} sim-years/s) ({seed_verdict})",
        f"simperf: megakernel vmap(64) {mk_vm['value']} vs stage-pipeline "
        f"{st_vm['value']} sim-years/s on the composed-techniques sweep = "
        f"{mk_gain:.2f}x ({mk_verdict})",
        f"simperf: megakernel Pallas path {mk_pal['value']} sim-years/s = "
        f"{pal_vs_seed:.0f}x the seed's per-step-kernel path "
        f"({SEED_PALLAS_YEARS_PER_S})",
        f"simperf: typed vmap(16) {ty_vm['value']} sim-years/s = "
        f"{ty_vs_seed:.1f}x the pre-campaign collapse "
        f"({SEED_TYPED_VMAP16_YEARS_PER_S}); techniques/typed gap "
        f"{ty_gap:.1f}x ({ty_verdict})",
        f"simperf: weak scaling [{weak['cells']} cells @ "
        f"{weak['device_count']} device(s)] typed {weak['per_device']} "
        f"sim-years/s per device (gate {WEAK_TYPED_GATE_YEARS_PER_S}) "
        f"({weak_verdict})",
    ]
