"""facility_ms: device time of the facility chain in the traced call, ms
(layer: engine facility chain).

The union of the intervals of the operations under `megakernel.facility`,
the fused Pallas kernel (`megakernel.facility.pallas`) and the preparation
around it included, operations named by the compiled module
(perfbench/scopes.py).  Moves `sim_years_per_s`."""
from perfbench import program_view


def read(run):
    return program_view.scope_ms(run, "megakernel.facility")
