"""demand_ms: device time of the demand scan in the traced call, ms
(layer: engine demand scan).

The union of the intervals of the operations under the program's
`megakernel.demand` scope (the scan `while` and every operation of its
body, counted once), operations named by the compiled module
(perfbench/scopes.py, perfbench/program_view.py).  Moves
`sim_years_per_s`."""
from perfbench import program_view


def read(run):
    return program_view.scope_ms(run, "megakernel.demand")
