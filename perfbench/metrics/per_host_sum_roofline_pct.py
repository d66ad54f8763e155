"""per_host_sum_roofline_pct: the per-host sums' share of the chip's
roofline in the traced call, % (layer: per-host sums).

The least time the chip could take for the sums' operations and bytes
(perfbench/per_host_sum_work.py, at the task and host counts of the
command line's cell and seed; HBM bandwidth bounds it) over
`per_host_sum_ms`, by `peaks.roofline_pct`.  One-scenario cells only: a
grid hoists one demand scan under all its scenarios, so a count per
scenario would read too high there, and such a run reads None, as does a
program without the scope.  Moves `sim_years_per_s`."""
from perfbench import fresh_scopes, peaks, per_host_sum_work


def read(run):
    if run.n_scenarios != 1:
        return None
    ms = fresh_scopes.scope_ms(run, "stage_per_host_sum")
    sizes = per_host_sum_work.command_line_sizes()
    if ms is None or sizes is None:
        return None
    ops, nbytes = per_host_sum_work.work(*sizes, run.n_steps)
    return peaks.roofline_pct(ops, nbytes, ms / 1e3, run.device_kind)[0]
