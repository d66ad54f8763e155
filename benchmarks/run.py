"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--smoke]

Prints `name,us_per_call,derived` CSV rows (one per benchmark) followed by
the per-claim validation verdicts each bench module derives from its rows.
Raw rows land in results/bench/*.json for EXPERIMENTS.md.

--smoke (the CI job in .github/workflows/tests.yml) runs every module on a
tiny grid (2-day horizon, shrunken topology) purely to catch sweep-API
regressions; the paper-claim checks are skipped since the dynamics are not
meaningful at that scale — only SUITE ERRORs fail the run.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.core import telemetry

from . import (bench_analytical_gap, bench_battery_capacity,
               bench_battery_regions, bench_climate, bench_combinations,
               bench_embodied, bench_optimal_battery, bench_renewables,
               bench_scaling, bench_simperf, bench_spatial, bench_tradeoffs,
               common, roofline)

MODULES = {
    "scaling": bench_scaling,                # paper Fig 5  (F1/F2)
    "battery_regions": bench_battery_regions,  # Fig 6      (F3)
    "battery_capacity": bench_battery_capacity,  # Fig 7/8  (F4)
    "tradeoffs": bench_tradeoffs,            # Fig 9/14/15  (F4/F5)
    "embodied": bench_embodied,              # Fig 10       (F3/F4)
    "combinations": bench_combinations,      # Fig 11/16-19 (F5/F6)
    "optimal_battery": bench_optimal_battery,  # Fig 12     (F6)
    "analytical_gap": bench_analytical_gap,  # §III/§VI-C   (F5)
    "spatial": bench_spatial,                # beyond-paper (§IX/§XI ext.)
    "climate": bench_climate,                # beyond-paper (thermal subsys.)
    "renewables": bench_renewables,          # beyond-paper (supply side)
    "simperf": bench_simperf,                # §VIII
    "roofline": roofline,                    # §Dry-run / §Roofline
}

HISTORY_FILE = os.path.join(os.path.dirname(bench_simperf.BENCH_FILE),
                            "BENCH_simperf.history.jsonl")


def _append_history(stamp: str):
    """One JSONL row per driver invocation that produced BENCH_simperf.json:
    the headline summary plus a UTC timestamp, so speed trajectories across
    PRs/machines are greppable without digging through CI artifacts."""
    try:
        with open(bench_simperf.BENCH_FILE) as f:
            summary = json.load(f)
    except OSError:
        return
    entry = dict(summary)
    entry.pop("rows", None)            # headline only; rows stay in the .json
    entry["timestamp"] = stamp
    with open(HISTORY_FILE, "a") as f:
        f.write(json.dumps(entry, default=float) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale region counts / horizons (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids, API-regression signal only (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        common.SMOKE = True
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")

    names = [args.only] if args.only else list(MODULES)
    print("name,us_per_call,derived")
    verdicts = []
    ran_ok = set()
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
            ran_ok.add(name)
            dt = time.time() - t0
            head = rows[0] if rows else {}
            derived = f"{head.get('metric','rows')}={head.get('value', len(rows))}"
            print(f"{name},{dt*1e6:.0f},{derived}", flush=True)
            if hasattr(mod, "check") and not args.smoke:
                verdicts += [f"[{name}] {v}" for v in mod.check(rows)]
        except Exception as e:  # keep the suite going; report the failure
            dt = time.time() - t0
            print(f"{name},{dt*1e6:.0f},ERROR:{type(e).__name__}:{e}",
                  flush=True)
            verdicts.append(f"[{name}] SUITE ERROR: {e}")
    if "simperf" in ran_ok:
        _append_history(stamp)
    tel = telemetry.get()
    if tel is not None and tel.events:   # STEAM_TELEMETRY=1 (CI bench-smoke)
        print(f"telemetry: {tel.export_chrome_trace()}", flush=True)
    print()
    print("=== paper-claim validation (F1-F6 + §III/§VIII) ===")
    for v in verdicts:
        print(v)
    bad = sum("FAIL" in v or "SUITE ERROR" in v for v in verdicts)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
