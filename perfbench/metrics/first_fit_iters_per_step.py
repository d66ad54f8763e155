"""first_fit_iters_per_step: first-fit placement-loop iterations per
simulated step, mean over the traced call's scenarios (layer: scheduler).

The program's counter `first_fit_iters` (summed over the run by the
scheduler stage) over the steps of the horizon, read from the output of
the traced program (perfbench/program_view.py).  Moves
`sim_years_per_s`."""
from perfbench import program_view


def read(run):
    counts = program_view.first_fit(run)
    if counts is None:
        return None
    iters, _, n_scenarios = counts
    return iters / n_scenarios / run.n_steps
