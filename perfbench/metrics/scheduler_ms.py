"""scheduler_ms: device time of the scheduler stage in the traced call, ms
(layer: scheduler).

The union of the intervals of the operations under `stage_scheduler`
(candidate selection, per-host sums, the first-fit loop, the table
writes), operations named by the compiled module (perfbench/scopes.py).
Moves `sim_years_per_s`."""
from perfbench import program_view


def read(run):
    return program_view.scope_ms(run, "stage_scheduler")
