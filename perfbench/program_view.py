"""What the per-layer readers of a traced run need from the program itself.

A reader gets only the run's `manifest.RunData`: the trace, the set-up
compile seconds, peak memory and the sizes.  The scope, first-fit and
set-up tracing metrics need more: the compiled module's text, to name the trace's operations
(perfbench/scopes.py); the first-fit counters of the traced call's
output; and the seconds set-up spent tracing and lowering.  `view()`
gathers them once per process, the first time a reader asks:

* the set-up's trace and lower seconds are the program's totals since
  compile activity was first watched (`telemetry.compile_watch`), read
  before anything below traces again;
* the program is rebuilt from the cell and `--seed` of the command line of
  perfbench/run.py, with the program's named scopes on as in the run, and
  compiled: the same module as set-up's, so the compile caches return
  the executable that was traced;
* that executable is called once on the same inputs, which are made from
  the seed, so its counters are those of the traced call.

The trace is then named in place (`scopes.attribute`), so the run's
breakdown names scopes too.  A trace whose operations already carry scope
paths (a `tf_op` stat) is not renamed.  Where the program lacks a counter
(an older program), the reader that needs it reads None.  A summary of
what the view cost and found is printed on standard error.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import NamedTuple, Optional

import numpy as np


class View(NamedTuple):
    hlo_text: Optional[str]          # compiled module of the traced program
    first_fit_iters: Optional[np.ndarray]   # [N] per scenario, or None
    first_fit_placed: Optional[np.ndarray]
    setup_trace_s: Optional[float]   # jaxpr tracing during set-up
    setup_lower_s: Optional[float]   # MLIR lowering during set-up


_VIEW: Optional[View] = None


def _setup_tracing():
    """(trace, lower) seconds since compile activity was first watched, or
    (None, None) for a program whose compile counter times neither."""
    from repro.core import telemetry
    try:
        watch = telemetry.CompileWatch(since_start=True)
    except TypeError:
        return None, None
    return watch.trace_seconds, watch.lower_seconds


def _counter(out, name: str):
    metrics = getattr(out, "metrics", out)  # SimState or SimResult
    value = getattr(metrics, name, None)
    return None if value is None else np.asarray(value, np.float64).ravel()


def build(cell, seed: int) -> View:
    """Rebuild `cell`'s program for `seed`, as perfbench/run.py builds it,
    and read the view from it; it becomes the process's view."""
    global _VIEW
    import jax
    from perfbench import generator, system, trace

    trace_s, lower_s = _setup_tracing()
    t0 = time.perf_counter()
    study = generator.study(cell.config, cell.traffic, seed)
    with trace.named_scopes():
        program = system.build(cell.config, cell.traffic, study)
        compiled = program.fn.lower(*program.args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*program.args))
    t2 = time.perf_counter()
    _VIEW = View(compiled.as_text(), _counter(out, "first_fit_iters"),
                 _counter(out, "first_fit_placed"), trace_s, lower_s)
    print(f"program_view: {cell.name} rebuilt in {t1 - t0:.3f} s, "
          f"called in {t2 - t1:.3f} s", file=sys.stderr)
    return _VIEW


def view() -> Optional[View]:
    """The process's view, built on first use from the command line
    (`--workload`, `--seed`); None where the command names no cell."""
    if _VIEW is not None:
        return _VIEW
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    args, _ = ap.parse_known_args(sys.argv[1:])
    if args.workload is None or args.seed is None:
        return None
    from perfbench import manifest
    return build(manifest.cell(args.workload), args.seed)


def named_trace(run):
    """`run.trace` with its device operations named by scope, or None
    where it has no device operations."""
    from perfbench import scopes
    tr = run.trace
    if tr is None or not any(d.ops for d in tr.devices):
        return None
    if any(o.path for d in tr.devices for o in d.ops):
        return tr
    v = view()
    if v is None or v.hlo_text is None:
        return None
    known = scopes.attribute(tr, v.hlo_text)
    cover = [scopes.coverage(d) for d in tr.devices]
    print(f"program_view: {100 * known:.2f}% of operations in the module, "
          f"{100 * min(cover):.3f}% of busy time under a named scope",
          file=sys.stderr)
    return tr


def scope_ms(run, scope: str):
    """Device time of `scope` in the traced call, ms, averaged over the
    devices that ran it; None where no operation is under it."""
    from perfbench import scopes
    tr = named_trace(run)
    if tr is None:
        return None
    times = [scopes.scope_ns(d, scope) for d in tr.devices]
    times = [t for t in times if t is not None]
    return sum(times) / len(times) / 1e6 if times else None


def first_fit(run):
    """(iterations, placements) summed over the scenarios of the traced
    call, and the scenario count; None where the program counts neither."""
    v = view()
    if v is None or v.first_fit_iters is None or v.first_fit_placed is None:
        return None
    return (float(v.first_fit_iters.sum()), float(v.first_fit_placed.sum()),
            v.first_fit_iters.size)
