"""Device time per program scope: a trace's operations named by the
compiled module.

A TPU v5e trace under JAX 0.9.0 names each device operation by its HLO
instruction and carries no scope metadata, so `trace.load` leaves every
operation's `path` empty there (perfbench/tests/surf_tiny.xplane.pb.gz).
The compiled module's text holds what is missing: each instruction carries
`metadata={op_name="jit(run)/megakernel.demand/while/body/..."}`, in which
the program's `stage_scope` names sit.  Instruction names are unique in a
module, so the map {instruction: op_name} names every operation of a trace
of that module.

Rules, kept here so that every change computes them alike:

* An operation keeps the scope path its trace gave it (the `tf_op` stat);
  an operation without one takes its instruction's `op_name`
  (`attribute`).  An instruction with no metadata stays unnamed.  A scope
  traced under a transformation is named inside the transformation's name
  (`vmap(vmap(megakernel.demand))` in a grid): such a part is read as the
  scope it wraps.
* A scope's time is the union of the intervals of the operations whose
  path names it or a scope inside it (`megakernel.facility` covers
  `megakernel.facility.pallas`; `stage_scheduler` the
  `stage_scheduler.*` parts): a `while` and the operations of its body
  count once (`scope_ns`).
* Coverage is the share of busy time in which some operation under a
  named program scope ran (`trace.innermost` is not `other`).
"""
from __future__ import annotations

import gzip
import re

from perfbench import trace

_WRAPPED = re.compile(r"^[\w-]+\((.+)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def read_text(path: str) -> str:
    """A compiled module's text, plain or gzipped (`.gz`)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def _unwrap(part: str) -> str:
    """`vmap(vmap(megakernel.demand))` -> `megakernel.demand`; a part that
    wraps no program scope (`jit(run)`) stays as it is."""
    inner = part
    while (m := _WRAPPED.match(inner)):
        inner = m.group(1)
    return inner if trace.innermost(inner) != "other" else part


def op_names(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction name: scope path}) of a compiled module's
    text (`compiled.as_text()`): every instruction, its path the `op_name`
    of its metadata with transformation-wrapped scopes unwrapped, or ''
    where it has none (copies and the like that the compiler adds)."""
    module, names, paths = None, {}, {}
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTRUCTION.match(line)
        if m:
            meta = _OP_NAME.search(line)
            op_name = meta.group(1) if meta else ""
            if op_name not in paths:
                paths[op_name] = "/".join(_unwrap(p)
                                          for p in op_name.split("/"))
            names[m.group(1)] = paths[op_name]
    if module is None:
        raise ValueError("no `HloModule` line: not a compiled module's text")
    return module, names


def module_names(xplane_path: str) -> list:
    """Names of the modules a trace's devices ran (`XLA Modules` events,
    named `<module>(<fingerprint>)`)."""
    from jax.profiler import ProfileData
    if xplane_path.endswith(".gz"):
        with gzip.open(xplane_path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out += [ev.name.split("(", 1)[0] for ev in line.events]
    return out


def attribute(tr, hlo_text: str) -> float:
    """Give each device operation of `tr` that has no scope path its
    instruction's, in place; returns the share of operations that are
    instructions of the module.  Raises where none is: the trace is of
    another program."""
    _, names = op_names(hlo_text)
    n_ops = n_known = 0
    for dev in tr.devices:
        for i, op in enumerate(dev.ops):
            n_ops += 1
            path = names.get(op.name)
            if path is None:
                continue
            n_known += 1
            if path and not op.path:
                dev.ops[i] = op._replace(path=path)
    if n_ops and not n_known:
        raise ValueError("no operation of the trace is an instruction of the "
                         "module: the trace is of another program")
    return n_known / n_ops if n_ops else 0.0


def _under(path: str, scope: str) -> bool:
    return any(p == scope or p.startswith(scope + ".")
               for p in path.split("/"))


def _union_where(device, keep) -> int | None:
    """Union of the intervals of the operations whose path `keep` accepts;
    `keep` is asked once per distinct path (a step's operations repeat
    their paths at every step)."""
    verdict = {}
    spans = []
    for o in device.ops:
        ok = verdict.get(o.path)
        if ok is None:
            ok = verdict[o.path] = keep(o.path)
        if ok:
            spans.append((o.start, o.start + o.dur))
    return trace._union_ns(spans) if spans else None


def scope_ns(device, scope: str):
    """Device time of `scope`, ns: the union of the intervals of the
    operations under it.  None where no operation is under it."""
    return _union_where(device, lambda path: _under(path, scope))


def coverage(device) -> float:
    """Share of the device's busy time under a named program scope."""
    named = _union_where(device,
                         lambda path: trace.innermost(path) != "other")
    return (named or 0) / device.busy_ns if device.busy_ns else 0.0
