"""first_fit_yield_pct: share of first-fit placement-loop iterations that
placed a task, % (layer: scheduler).

100 x `first_fit_placed` / `first_fit_iters`, the program's counters
summed over the traced call's scenarios (perfbench/program_view.py).
Moves `sim_years_per_s`."""
from perfbench import program_view


def read(run):
    counts = program_view.first_fit(run)
    if counts is None or counts[0] == 0:
        return None
    iters, placed, _ = counts
    return 100.0 * placed / iters
