#!/usr/bin/env python3
"""Record the small device trace that the trace reduction is tested on.

    python perfbench/record_trace.py <out.xplane.pb.gz>

On the chip: one traced call of `simulate` over the SURF configuration cut
to 8 hosts and 1 day (the program's named scopes on, as in every benchmark
run), written gzipped, and a summary of its planes printed; the trace
reduction's test can then read a real device plane on the CPU.
`tiny_cell` also serves the tests that run a cell on the CPU.
"""
import copy
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


# Cells whose files are kept under perfbench/ without an entry in
# BENCHMARK.json, as fixtures of the tests that run a cell on the CPU and
# for the entry that adds them back: (configuration file, traffic).
# marconi-composed ran correct on the chip, but its rate spread too widely
# between runs of one seed to hold a bound (PERF.md, Open questions).
DEFERRED = {
    "marconi-composed": ("perfbench/configs/marconi.json", "single"),
}


def tiny_cell(name: str = "surf-composed", days: float = 1.0,
              hosts: int = 8, regions: int = 3, chips: int = 1):
    """A cell cut to a size a test can hold: `hosts` hosts, `days` days,
    `regions` carbon regions for a grid.  `name` is a cell of
    BENCHMARK.json or of DEFERRED."""
    from perfbench import manifest
    bench = manifest.load(ROOT)
    if name in DEFERRED:
        cell = manifest.assemble(name, *DEFERRED[name], chips, bench, ROOT)
    else:
        cell = manifest.cell(name, ROOT, bench)
    cfg = copy.deepcopy(cell.config)
    cfg["workload"]["horizon_days"] = days
    cfg["workload"]["scale"] = hosts / cfg["workload"]["n_hosts"]
    traffic = copy.deepcopy(cell.traffic)
    for ax in traffic.get("axes", []):
        if "trace" in ax:
            ax["trace"] = regions
    return cell._replace(config=cfg, traffic=traffic, chips=chips)


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from perfbench import generator, system, trace
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the recorded trace must hold a device plane")
    cell = tiny_cell()
    study = generator.study(cell.config, cell.traffic, 0)
    with trace.named_scopes():
        program = system.build(cell.config, cell.traffic, study)
        jax.block_until_ready(program.fn(*program.args))
        logdir = tempfile.mkdtemp(prefix="perfbench-record-")
        _, path = trace.capture(program.fn, program.args, logdir)
    with open(path, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    shutil.rmtree(logdir, ignore_errors=True)
    from jax.profiler import ProfileData
    with gzip.open(out, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            print(f"plane {plane.name!r} line {line.name!r}: {len(evs)} events")
            for ev in evs[:3]:
                print("   ", ev.name, ev.duration_ns, dict(ev.stats))
    tr = trace.load(out)
    for d in tr.devices:
        print(d.name, "ops", len(d.ops), "busy_ns", d.busy_ns, "window",
              trace.window_ns(d), trace.top_ops(d)[:5])
    print("bytes", Path(out).stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
