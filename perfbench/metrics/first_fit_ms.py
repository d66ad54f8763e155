"""first_fit_ms: device time of the first-fit placement loop in the traced
call, ms (layer: scheduler).

The union of the intervals of the operations under
`stage_scheduler.first_fit` (the placement `while_loop` and its body),
operations named by the compiled module (perfbench/scopes.py).  A program
without that scope reads None.  Moves `sim_years_per_s`."""
from perfbench import program_view


def read(run):
    return program_view.scope_ms(run, "stage_scheduler.first_fit")
