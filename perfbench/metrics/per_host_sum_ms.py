"""per_host_sum_ms: device time of the per-host sums in the traced call,
ms (layer: per-host sums).

The union of the intervals of the operations under `stage_per_host_sum`
(the running mask, the `[T, 2]` column stack and the contraction of both
sums: free capacity under `stage_scheduler.free_capacity`, utilization
under `stage_it_power`), operations named by the compiled module
(perfbench/scopes.py, perfbench/program_view.py; where the compile cache
handed the run an older program's names, by a fresh compile of the same
module, perfbench/fresh_scopes.py).  A program without that scope reads None.
Moves `sim_years_per_s`."""
from perfbench import fresh_scopes


def read(run):
    return fresh_scopes.scope_ms(run, "stage_per_host_sum")
