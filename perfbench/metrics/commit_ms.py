"""commit_ms: device time of the scheduler's table writes in the traced
call, ms (layer: scheduler).

The union of the intervals of the operations under
`stage_scheduler.commit` (the placed rows' status, host, first start and
host speed written into the task table after the placement loop),
operations named by the compiled module (perfbench/scopes.py).  A program
without that scope reads None.  Moves `sim_years_per_s`."""
from perfbench import program_view


def read(run):
    return program_view.scope_ms(run, "stage_scheduler.commit")
