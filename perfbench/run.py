#!/usr/bin/env python3
"""Benchmark of the simulator on the chip: one cell, one run.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout.  The cell is an entry of `workloads` in
BENCHMARK.json; its configuration, traffic, limits and per-layer metrics
are files found by name (perfbench/manifest.py).  One run:

1. checks that JAX sees a TPU with as many chips as the cell asks for,
   and exits non-zero with no result otherwise;
2. makes the deployment and its scenarios from `--seed` on the host, and
   the program's tables from them;
3. compiles the cell's one program (from the persistent compile cache in
   `<checkout>/.jax_cache`, or `JAX_COMPILATION_CACHE_DIR`) and warms it
   with one call: the end of that call ends set-up (`setup_s`);
4. with `--trace 0`, calls it back to back, each call ending in
   `block_until_ready`, until `--seconds` have passed, with no compile
   allowed in that window; with `--trace 1`, traces one more call and
   reduces the trace to the per-layer metrics;
5. holds what the last call returned against the plain reference
   (perfbench/reference.py, perfbench/compare.py) and prints each number
   beside its limit on standard error;
6. prints one JSON line: correct, attempted, failed, metrics, device, and
   with `--trace 1` a breakdown; the compared numbers come last, under
   `checks`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
DAYS_PER_YEAR = 365.25


def check_devices(chips: int, need_tpu: bool = True):
    """The devices the cell runs on; exits non-zero where JAX finds no TPU
    or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if need_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {devs[0].platform!r} "
                         f"({devs[0].device_kind}); this benchmark runs "
                         f"only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs


def peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def n_failed(prog: dict) -> int:
    """Scenarios whose result has a non-finite number."""
    import numpy as np
    bad = None
    for k, v in prog.items():
        if k in ("first_start", "finish"):
            continue
        b = ~np.isfinite(np.asarray(v, np.float64))
        bad = b if bad is None else bad | b
    return int(bad.sum())


def run_cell(cell, seed: int, seconds: float, traced: bool,
             need_tpu: bool = True, build=None) -> dict:
    """One run of a cell; returns the result line as a dict.

    `build(config, traffic, study)` makes the program under test
    (system.build); tests put a broken program in its place."""
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.core import telemetry

    from perfbench import compare, generator, manifest, system, trace

    devices = check_devices(cell.chips, need_tpu)
    enable_compile_cache()
    # every program the run compiles is cached, however short its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    build = build or system.build

    study = generator.study(cell.config, cell.traffic, seed)
    with trace.named_scopes(), telemetry.compile_watch() as setup_watch:
        program = build(cell.config, cell.traffic, study)
        out = jax.block_until_ready(program.fn(*program.args))
        setup_s = time.perf_counter() - T_START
        compile_s = setup_watch.seconds

        n_scen = study.n_scenarios
        years = n_scen * cell.config["workload"]["horizon_days"] \
            / DAYS_PER_YEAR
        breakdown = None
        if traced:
            logdir = tempfile.mkdtemp(prefix="perfbench-trace-")
            out, path = trace.capture(program.fn, program.args, logdir)
            calls = 1
        else:
            with telemetry.compile_watch() as window_watch:
                t_first = time.perf_counter()
                calls = 0
                while True:
                    out = jax.block_until_ready(program.fn(*program.args))
                    calls += 1
                    t_last = time.perf_counter()
                    if t_last - t_first >= seconds:
                        break
            if window_watch.count:
                raise RuntimeError(f"{window_watch.count} compiles inside "
                                   f"the measured window")
    peak = peak_bytes(devices[:cell.chips])
    prog = system.outputs(program, out, study)
    del out, program
    failed = n_failed(prog) * calls

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if traced:
        tr = trace.load(path)
        shutil.rmtree(logdir, ignore_errors=True)
        run = manifest.RunData(tr, compile_s, peak, devices[0].device_kind,
                               n_scen, study.deployment.n_steps)
        metrics = {}
        for m in cell.per_layer:
            val = manifest.reader(m["name"]).read(run)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        if tr.devices:
            device["busy_s"] = sum(d.busy_ns for d in tr.devices) \
                / len(tr.devices) / 1e9
            device["window_s"] = max(trace.window_ns(d)
                                     for d in tr.devices) / 1e9
            dev = max(tr.devices, key=lambda d: d.busy_ns)
            breakdown = {"device_ops": trace.top_ops(dev),
                         "idle_gaps": trace.idle_gaps(dev, tr.host)}
    else:
        values = {"sim_years_per_s": calls * years / (t_last - t_first),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    dem, tasks, fac, owner = compare.reference_for(cell.config, study)
    nums = compare.numbers(prog, dem, tasks, fac, owner,
                           study.deployment.n_steps * study.deployment.dt_h)
    correct, checks = compare.verdict(nums, cell.limits)
    correct = correct and failed == 0
    result = {"correct": correct, "attempted": calls * n_scen,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from perfbench import manifest

    cell = manifest.cell(args.workload, ROOT)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
