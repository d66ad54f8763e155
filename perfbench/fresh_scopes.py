"""Device time of a scope that the compile cache may hide.

JAX's persistent compile cache keys a program by its module with the
debug information stripped, and the named scopes travel in that
information (the `op_name` metadata).  So a cache filled by an older
program that differs from the newer one only in its scopes hands the
newer one the older executable, metadata and all:
perfbench/program_view.py then names the trace's operations by the older
scopes, and a scope that only the newer program has reads nothing.  Where
the machine sets `JAX_COMPILATION_CACHE_DIR`, one cache may serve two
checkouts, and the older executable is then the parent's.

`scope_ms` then compiles the program of the command line's cell and seed
once more with the persistent cache off, which gives the instructions
under the scopes of the code that runs, and names the trace by that
compile only where it is the executable that ran: its module, metadata
stripped, equals the one program_view read (so its instruction names are
those of the trace).  Otherwise it reads nothing.  What it cost and found
is printed on standard error.
"""
from __future__ import annotations

import argparse
import re
import sys
import time

_TEXTS: dict = {}
# an instruction, a computation's first line, or a computation's end
_COMPUTATION = re.compile(r"^\s*(?:ROOT\s+%|%|ENTRY\s|\}$)")


def command_line():
    """(cell, seed) that perfbench/run.py's command line names, or None."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    args, _ = ap.parse_known_args(sys.argv[1:])
    if args.workload is None or args.seed is None:
        return None
    return args.workload, args.seed


def instructions(hlo_text: str) -> str:
    """A compiled module's computations and instructions without their
    metadata (op names, source locations): the module's other lines, its
    name and the tables of files and stack frames, are left out."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r", stack_frame_id=\d+", "", text)
    return "\n".join(line for line in text.splitlines()
                     if _COMPUTATION.match(line))


def fresh_text():
    """Compiled module text of the program of the command line's cell and
    seed, compiled with the persistent cache off; None where the command
    line names no cell."""
    key = command_line()
    if key is None:
        return None
    if key not in _TEXTS:
        _TEXTS[key] = _compile(*key)
    return _TEXTS[key]


def _compile(workload: str, seed: int) -> str:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from perfbench import generator, manifest, system, trace
    t0 = time.perf_counter()
    cell = manifest.cell(workload)
    study = generator.study(cell.config, cell.traffic, seed)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with trace.named_scopes():
            program = system.build(cell.config, cell.traffic, study)
            text = program.fn.lower(*program.args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    print(f"fresh_scopes: {workload} compiled again without the persistent "
          f"cache in {time.perf_counter() - t0:.3f} s", file=sys.stderr)
    return text


def fresh_names(tr, ran_text: str, fresh: str):
    """{instruction: scope path} of the fresh module `fresh` where it is
    the module `ran_text` that ran, metadata aside; else None.  Prints
    which, and the share of the trace `tr`'s operations it names."""
    from perfbench import scopes
    same = instructions(fresh) == instructions(ran_text)
    names = scopes.op_names(fresh)[1]
    ops = [o.name for d in tr.devices for o in d.ops]
    known = sum(name in names for name in ops) / len(ops)
    print(f"fresh_scopes: module {'equal to' if same else 'NOT equal to'} "
          f"the one that ran, metadata aside; {100 * known:.2f}% of "
          f"operations in it", file=sys.stderr)
    return names if same else None


def scope_ms(run, scope: str):
    """Device time of `scope` in the traced call, ms, averaged over the
    devices that ran it: program_view's reading, or where that finds no
    operation under `scope`, the union of the intervals of the operations
    that the fresh compile puts under it (`fresh_names`).  None where
    neither finds one."""
    from perfbench import program_view, scopes, trace
    ms = program_view.scope_ms(run, scope)
    if ms is not None:
        return ms
    if run.trace is None or not any(d.ops for d in run.trace.devices):
        return None
    ran, fresh = program_view.view(), fresh_text()
    if ran is None or ran.hlo_text is None or fresh is None:
        return None
    names = fresh_names(run.trace, ran.hlo_text, fresh)
    if names is None:
        return None
    times = []
    for dev in run.trace.devices:
        spans = [(o.start, o.start + o.dur) for o in dev.ops
                 if scopes._under(names.get(o.name, ""), scope)]
        if spans:
            times.append(trace._union_ns(spans))
    return sum(times) / len(times) / 1e6 if times else None
