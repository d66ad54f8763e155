#!/usr/bin/env python3
"""Bring-up smoke run of the simulator on one TPU, at published size.

    python chip_smoke.py [--four-chips]

One process, in this order:

1. device   : the JAX version and what JAX found; anything but a TPU exits
              non-zero before any work (there is no CPU fallback);
2. kernels  : the main-path Pallas kernels, compiled (not interpreted), at
              the SURF-calibrated width, against their oracles in
              kernels/ref.py at the tolerances of tests/test_kernels.py and
              tests/test_megakernel.py;
3. deployment: the SURF-calibrated datacenter (277 hosts x 16 cores, its
              arrival density; the horizon cut from the spec's 124 days to
              62, and the cut printed)
              under the composed techniques (cooling + pricing + PV +
              battery) with the Pallas kernels on the path: summarize(
              simulate(...)) on both step executors, then one 8-region
              sweep_grid;
4. agreement: the same deployment at a 7-day horizon, on the chip and on
              the host CPU in this process, jnp and Pallas paths alike,
              held to the goldens' tolerance (rtol 1e-4).

The last line is one JSON object naming the device.  Any failed phase
raises, so the script exits non-zero and prints no such line.

--four-chips runs only the multi-chip path: `sweep_grid(...,
executor="shard_map")` over 4 x 2 region cells on four chips, against the
chunked single-device run of the same cells.

The compile cache is `JAX_COMPILATION_CACHE_DIR` if set, else
`<checkout>/.jax_cache` (repro.compile_cache), so a second run reports
fewer compile seconds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SURF_DAYS = 124.0           # the SURF-calibrated spec's published horizon
# The per-step work of the demand scan grows with the task count, which
# grows with the horizon, so a run costs ~days^2: on one TPU v5e 31 days
# took 23.4 s, 62 days 89.1 s and the full 124 days 347.3 s per program.
# Half the horizon keeps the three deployment programs in ~5 min.
DAYS = 62.0
AGREE_DAYS = 7.0            # short enough for the host CPU at full width
DT_H = 0.25
REGIONS = 8
RTOL = 1e-4                 # tests/conftest.py golden tolerance


def log(msg: str) -> None:
    print(msg, flush=True)


def device_phase(n_chips: int):
    import jax
    log(f"jax {jax.__version__}")
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {d0.platform}; this run needs "
                         f"the chip and has no CPU fallback")
    if len(devs) < n_chips:
        raise SystemExit(f"needs {n_chips} TPU chips, found {len(devs)}")
    return d0, len(devs)


def _close(name, got, want, rtol, atol=0.0):
    import numpy as np
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64),
                               rtol=rtol, atol=atol, err_msg=name)


def kernel_phase(n_hosts: int, n_steps: int):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.bench_simperf import _shared_traces, _technique_cfg
    from repro.carbontraces.synthetic import make_region_traces
    from repro.core import (BatteryConfig, CoolingConfig, SimConfig,
                            build_step_inputs, facility_totals_from_flows)
    from repro.core.config import PowerModelConfig
    from repro.core.power import host_power_kw
    from repro.core.thermal import cooling_step
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    cpu_cfg = PowerModelConfig(80.0, 250.0, "sqrt")
    gpu_cfg = PowerModelConfig(40.0, 300.0, "linear")
    ccfg = CoolingConfig(enabled=True)

    def hosts(*shape):
        return (rng.uniform(0, 1, shape).astype(np.float32),
                rng.uniform(0, 1, shape).astype(np.float32),
                rng.integers(0, 4, shape).astype(np.float32),
                (rng.uniform(size=shape) < 0.8).astype(np.float32))

    cu, gu, ng, on = hosts(n_hosts)
    p, dc, carbon = ops.fused_power_carbon(cu, gu, ng, on, 350.0, DT_H,
                                           cpu_cfg, gpu_cfg)
    p_r, dc_r, carbon_r = ref.fused_power_carbon(
        cu, gu, ng, on, 350.0, DT_H, cpu_idle=80.0, cpu_max=250.0,
        cpu_curve="sqrt", gpu_idle=40.0, gpu_max=300.0, gpu_curve="linear")
    _close("power_carbon.power", p, p_r, 1e-5, 1e-6)
    _close("power_carbon.dc", dc, dc_r, 1e-4)
    _close("power_carbon.carbon", carbon, carbon_r, 1e-4)
    log(f"kernels: fused_power_carbon H={n_hosts} ok")

    def facility_oracle(cu, gu, ng, on, wb, sp):
        p = host_power_kw(cu, gu, ng, on, cpu_cfg, gpu_cfg)
        it = jnp.sum(p)
        cool, water = cooling_step(it, wb, ccfg, setpoint_c=sp)
        return p, it, cool, water

    def check_facility(name, got, want):
        for k, g, w, tol in zip(("power", "it", "cooling", "water"), got,
                                want, ((1e-5, 1e-6), (1e-4, 0.0),
                                       (1e-4, 1e-6), (1e-4, 1e-6))):
            _close(f"{name}.{k}", g, w, *tol)

    got = ops.facility_power(cu, gu, ng, on, 27.0, 24.0, cpu_cfg, gpu_cfg,
                             ccfg)
    check_facility("facility_power", got,
                   facility_oracle(cu, gu, ng, on, 27.0, 24.0))
    log(f"kernels: fused_facility_power H={n_hosts} ok")

    cu, gu, ng, on = hosts(REGIONS, n_hosts)
    wb = rng.uniform(5.0, 35.0, REGIONS).astype(np.float32)
    sp = rng.uniform(18.0, 28.0, REGIONS).astype(np.float32)
    got = ops.facility_power_batched(cu, gu, ng, on, wb, sp, cpu_cfg,
                                     gpu_cfg, ccfg)
    for r in range(REGIONS):
        check_facility(f"facility_power_batched[{r}]",
                       [g[r] for g in got],
                       facility_oracle(cu[r], gu[r], ng[r], on[r], wb[r],
                                       sp[r]))
    log(f"kernels: facility_power_batched R={REGIONS} H={n_hosts} ok")

    # the megakernel's facility chain at the full horizon, every technique
    # composed; f32 storage is held to the oracle like the f32 test, the
    # quantized stores (battery off, as in their test) to their envelopes
    cfg = _technique_cfg(SimConfig(dt_h=DT_H, n_steps=n_steps))
    ci = make_region_traces(n_steps, DT_H, 1, 0)[0]
    it_kw = jnp.asarray(rng.uniform(40.0, 70.0, n_steps), jnp.float32)

    def totals(cfg, store):
        inputs = build_step_inputs(ci, cfg, _shared_traces(n_steps))
        args = (it_kw, inputs.ci, inputs.wet_bulb_c, inputs.price,
                inputs.price_lo, inputs.price_hi, inputs.pv_cf,
                inputs.batt_threshold, inputs.ci_rising)
        flows = ref.fused_facility_chain(*args, cfg.dt_h, cfg)
        want = facility_totals_from_flows(flows, inputs, cfg)
        return ops.facility_totals(*args, cfg, trace_store=store), want

    got, want = totals(cfg, "f32")
    for k in want:
        _close(f"facility_totals[f32].{k}", got[k], want[k], 1e-4, 1e-3)
    log(f"kernels: fused_facility_totals S={n_steps} f32 ok")
    cfg_nb = cfg.replace(battery=BatteryConfig(enabled=False))
    for store, rel in (("bf16", 5e-3), ("int8", 1e-2)):
        got, want = totals(cfg_nb, store)
        for k in ("grid_energy", "it_energy", "dc_energy", "op_carbon",
                  "cooling_energy", "pv_energy", "energy_cost"):
            err = abs(float(got[k]) - float(want[k])) / max(
                abs(float(want[k])), 1e-6)
            if not err <= rel:
                raise AssertionError(
                    f"facility_totals[{store}].{k}: rel err {err:.2e} > {rel}")
        log(f"kernels: fused_facility_totals S={n_steps} {store} ok")


def deployment(days: float, backend: str, use_pallas: bool):
    """(tasks, hosts, meta, cfg, dyn, traces) of the SURF-calibrated spec
    at its published width and arrival density over `days`."""
    from benchmarks.bench_simperf import _shared_traces, _technique_cfg
    from repro.carbontraces.synthetic import make_region_traces
    from repro.core import SimConfig
    from repro.workloads.synthetic import make_workload

    tasks, hosts, _, meta = make_workload("surf", scale=1.0, n_tasks_cap=None,
                                          horizon_days=days, dt_h=DT_H)
    n_steps = int(round(days * 24 / DT_H))
    cfg = _technique_cfg(SimConfig(dt_h=DT_H, n_steps=n_steps,
                                   embodied=meta["embodied"]))
    cfg = cfg.replace(backend=backend, use_pallas=use_pallas)
    traces = make_region_traces(n_steps, DT_H, REGIONS, 0)
    return tasks, hosts, meta, cfg, _shared_traces(n_steps), traces


def timed(label: str, fn, *args):
    """Compile fn ahead of time (compile seconds as telemetry counts them),
    then run it once: the steady seconds hold no compilation."""
    import jax
    from repro.core import telemetry
    with telemetry.compile_watch() as w:
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        build = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    steady = time.perf_counter() - t0
    log(f"{label}: compile_s={w.seconds:.3f} compiles={w.count} "
        f"lower_and_compile_s={build:.3f} steady_s={steady:.3f}")
    return out


def _finite(name, res):
    import jax
    import numpy as np
    for path, leaf in jax.tree_util.tree_leaves_with_path(res):
        if not np.all(np.isfinite(np.asarray(leaf, np.float64))):
            raise AssertionError(f"{name}: non-finite {path}")


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def deployment_phase(days: float, device):
    import jax
    import numpy as np
    from repro.core import simulate, summarize, sweep_grid, trace_axis

    if days < SURF_DAYS:
        log(f"reduced: horizon_days {SURF_DAYS:g} -> {days:g}")
    results = {}
    for backend in ("stage-pipeline", "megakernel"):
        tasks, hosts, meta, cfg, dyn, traces = deployment(days, backend,
                                                          True)
        res = timed(f"deployment[{backend}]", lambda tr: summarize(
            simulate(tasks, hosts, tr, cfg, dyn=dict(dyn))[0], cfg),
            traces[0])
        _finite(backend, res)
        results[backend] = res
        log(f"deployment[{backend}]: hosts={meta['n_hosts']} "
            f"tasks={meta['n_tasks']} steps={cfg.n_steps} "
            f"total_carbon_kg={float(res.total_carbon_kg):.6f} "
            f"grid_energy_kwh={float(res.grid_energy_kwh):.6f} "
            f"tasks_finished={float(res.n_done):.0f} "
            f"peak_bytes_in_use={_peak_bytes(device)}")
    # the sweep below runs the megakernel deployment of the last iteration
    a, b = results["stage-pipeline"], results["megakernel"]
    for k in a._fields:
        if getattr(a, k) is not None:
            _close(f"megakernel vs stage-pipeline: {k}", getattr(b, k),
                   getattr(a, k), RTOL, 1e-8)
    log(f"deployment: both executors agree (rtol {RTOL:g})")

    res = timed(f"sweep_grid[{REGIONS} regions, megakernel]",
                lambda trs: sweep_grid(tasks, hosts, cfg, [trace_axis(trs)],
                                       dyn=dict(dyn), jit=False), traces)
    _finite("sweep", res)
    if np.shape(res.total_carbon_kg) != (REGIONS,):
        raise AssertionError(f"sweep shape {np.shape(res.total_carbon_kg)}")
    _close("sweep region 0 vs single run", res.total_carbon_kg[0],
           b.total_carbon_kg, RTOL)
    log(f"sweep_grid: total_carbon_kg="
        f"{[round(float(x), 3) for x in res.total_carbon_kg]} "
        f"peak_bytes_in_use={_peak_bytes(device)}")


def agreement_phase():
    import jax
    import numpy as np
    from repro.core import simulate, summarize

    cpu = jax.devices("cpu")[0]
    for backend, use_pallas in (("stage-pipeline", False),
                                ("stage-pipeline", True),
                                ("megakernel", True)):
        label = f"{backend}, {'pallas' if use_pallas else 'jnp'}"
        out = {}
        for where in ("tpu", "cpu"):
            with jax.default_device(jax.devices()[0] if where == "tpu"
                                    else cpu):
                tasks, hosts, meta, cfg, dyn, traces = deployment(
                    AGREE_DAYS, backend, use_pallas)
                fn = jax.jit(lambda tr: summarize(
                    simulate(tasks, hosts, tr, cfg, dyn=dict(dyn))[0], cfg))
                res = jax.block_until_ready(fn(traces[0]))
            _finite(f"{label} on {where}", res)
            out[where] = res
        worst = 0.0
        for k in out["tpu"]._fields:
            t, c = getattr(out["tpu"], k), getattr(out["cpu"], k)
            if t is None:
                continue
            _close(f"agreement[{label}] chip vs host CPU: {k}", t, c, RTOL,
                   1e-8)
            c64 = np.asarray(c, np.float64)
            worst = max(worst, float(np.max(
                np.abs(np.asarray(t, np.float64) - c64)
                / np.maximum(np.abs(c64), 1e-8))))
        log(f"agreement[{label}]: {AGREE_DAYS:g} days, "
            f"{meta['n_hosts']} hosts, {meta['n_tasks']} tasks: chip == host "
            f"CPU within rtol {RTOL:g} (max rel diff {worst:.3e})")


def four_chip_phase(days: float, cells_per_chip: int = 2):
    import jax
    import numpy as np
    from repro.carbontraces.synthetic import make_region_traces
    from repro.core import sweep_grid, trace_axis

    ndev = jax.device_count()
    tasks, hosts, meta, cfg, dyn, _ = deployment(days, "megakernel", True)
    traces = make_region_traces(cfg.n_steps, DT_H, ndev * cells_per_chip, 0)
    if days < SURF_DAYS:
        log(f"reduced: horizon_days {SURF_DAYS:g} -> {days:g}")
    axes = [trace_axis(traces)]
    t0 = time.perf_counter()
    chunked = jax.block_until_ready(
        sweep_grid(tasks, hosts, cfg, axes, dyn=dict(dyn)))
    log(f"four-chips: chunked single-device sweep of {len(traces)} cells "
        f"in {time.perf_counter() - t0:.3f}s (compile included)")
    t0 = time.perf_counter()
    sharded = jax.block_until_ready(
        sweep_grid(tasks, hosts, cfg, axes, dyn=dict(dyn),
                   executor="shard_map"))
    log(f"four-chips: shard_map sweep in {time.perf_counter() - t0:.3f}s "
        f"(compile included)")
    placed = {s.device for s in sharded.total_carbon_kg.addressable_shards}
    if len(placed) != ndev:
        raise AssertionError(f"cells landed on {len(placed)} devices, "
                             f"not {ndev}")
    log(f"four-chips: cells on devices {sorted(d.id for d in placed)}")
    bitwise = True
    for k in chunked._fields:
        a, b = getattr(chunked, k), getattr(sharded, k)
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        bitwise &= bool(np.array_equal(a, b))
        # a partitioned program may round a horizon-long sum differently in
        # the last places (as XLA:CPU does, tests/test_grid.py): 1e-6 is
        # about eight f32 ulps, far below any simulated effect
        _close(f"shard_map vs chunked: {k}", b, a, 1e-6, 1e-8)
    log(f"four-chips: shard_map == chunked "
        f"{'bitwise' if bitwise else 'within rtol 1e-6 (not bitwise)'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map sweep on four chips")
    args = ap.parse_args(argv)

    n_chips = 4 if args.four_chips else 1
    device, count = device_phase(n_chips)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.ops import resolved_interpret
    log(f"compile cache: {enable_compile_cache()}")
    if resolved_interpret():
        raise SystemExit("Pallas kernels resolved to interpret mode")
    log("kernels: interpret=False (compiled for the chip)")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(DAYS)
    else:
        from repro.workloads.synthetic import SURF
        kernel_phase(SURF.n_hosts, int(SURF_DAYS * 24 / DT_H))
        deployment_phase(DAYS, device)
        agreement_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {"platform": device.platform,
                                             "kind": device.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
