"""trace_lower_s: seconds set-up spent tracing the program to a jaxpr and
lowering it to MLIR (layer: compile).

The program's compile counter (`telemetry.compile_watch`: the
`jax.monitoring` jaxpr-trace and MLIR-lowering durations) from the start
of set-up, read before anything traces again
(perfbench/program_view.py).  Work that no compile cache saves.  Moves
`setup_s`."""
from perfbench import program_view


def read(run):
    v = program_view.view()
    if v is None or v.setup_trace_s is None:
        return None
    return v.setup_trace_s + v.setup_lower_s
