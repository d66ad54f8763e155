#!/usr/bin/env python3
"""Record a small device trace with the compiled module it ran, the pair
that the scope attribution (perfbench/scopes.py) is tested on.

    python perfbench/record_scoped_trace.py <out prefix>

On the chip: the SURF cell cut to 8 hosts and 1 day
(`record_trace.tiny_cell`), compiled with the program's named scopes on,
called once under the profiler.  Writes `<out prefix>.xplane.pb.gz` (the
trace) and `<out prefix>.hlo.txt.gz` (`compiled.as_text()` of the very
executable traced), then prints the scope times and the coverage.
"""
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(prefix: str) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from perfbench import generator, record_trace, scopes, system, trace
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the recorded trace must hold a device plane")
    cell = record_trace.tiny_cell()
    study = generator.study(cell.config, cell.traffic, 0)
    with trace.named_scopes():
        program = system.build(cell.config, cell.traffic, study)
        compiled = program.fn.lower(*program.args).compile()
    jax.block_until_ready(compiled(*program.args))
    logdir = tempfile.mkdtemp(prefix="perfbench-record-")
    _, path = trace.capture(compiled, program.args, logdir)
    xplane, hlo = f"{prefix}.xplane.pb.gz", f"{prefix}.hlo.txt.gz"
    with open(path, "rb") as f, gzip.open(xplane, "wb") as g:
        g.write(f.read())
    shutil.rmtree(logdir, ignore_errors=True)
    with gzip.open(hlo, "wt") as g:
        g.write(compiled.as_text())

    tr = trace.load(xplane)
    text = scopes.read_text(hlo)
    print("modules", scopes.module_names(xplane), "hlo module",
          scopes.op_names(text)[0])
    print("operations in the module", scopes.attribute(tr, text))
    for d in tr.devices:
        print(d.name, "ops", len(d.ops), "busy_ns", d.busy_ns, "window",
              trace.window_ns(d), "coverage", scopes.coverage(d))
        for scope in ("megakernel.demand", "stage_scheduler",
                      "stage_scheduler.first_fit", "megakernel.facility"):
            print("  ", scope, scopes.scope_ns(d, scope))
        print("  ", trace.top_ops(d)[:6])
    print("bytes", Path(xplane).stat().st_size, Path(hlo).stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
