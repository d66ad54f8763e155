"""From a profiler trace (`.xplane.pb`) to per-layer device numbers.

`capture` traces one call of the compiled program; `reduce` reads the
trace with nothing but JAX (`jax.profiler.ProfileData`).

Rules of the reduction, kept here so that every PR computes them alike:

* Device planes are the planes named `/device:<TPU|GPU>:<n>`; their
  operation events are those of the line `XLA Ops`.
* The window is the host span `perfbench.call` that `capture` wraps around
  the call; device events are clipped to it.
* Busy time is the union of the intervals in which an operation runs on a
  device; idle share = 1 - busy / window.
* Each operation is attributed by the scope path in its name metadata
  (the `tf_op` stat, which carries `jax.named_scope` and jit names).  A TPU
  v5e trace under JAX 0.9.0 carries no such stat on its device operations
  (perfbench/tests/surf_tiny.xplane.pb.gz), so there every operation is
  `other` until the reduction maps instruction names to the compiled
  module's `op_name` metadata.  Of the
  program's scopes (`SCOPES` and every `stage_*`), the innermost that the
  path names gets the whole operation: a fusion that mixes scopes counts
  once, under the innermost scope it names.  An operation that names none
  is `other`.
* A scope's time sums the durations of its operations.  Scopes that nest
  report inclusive time: a query for `megakernel.demand` covers the
  `stage_*` scopes inside it.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import os
import re
from typing import NamedTuple

CALL_SPAN = "perfbench.call"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def capture(fn, args, logdir: str):
    """Run `fn(*args)` once under the profiler; returns (out, xplane path)."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation(CALL_SPAN):
            out = jax.block_until_ready(fn(*args))
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{logdir}")
    return out, paths[-1]


class Op(NamedTuple):
    name: str       # HLO operation name
    path: str       # scope path of its name metadata ('' when absent)
    start: int      # ns, on the trace's clock
    dur: int        # ns


class Device(NamedTuple):
    name: str
    ops: list        # [Op], clipped to the window
    busy_ns: int
    window: tuple    # (start_ns, end_ns)


class Trace(NamedTuple):
    devices: list    # [Device]
    host: list       # [(name, start_ns, end_ns)] host-side events


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def _union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def load(path: str) -> Trace:
    """Parse the trace (`.xplane.pb`, or gzipped `.xplane.pb.gz`) into
    device operations and host events."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    host, raw = [], []
    window = None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    path = _stat(ev, "tf_op") or _stat(ev, "name") or ""
                    # a TPU names the event by its whole HLO instruction
                    # text; the instruction's name is what precedes " = "
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append(Op(name, str(path), int(ev.start_ns),
                                  int(ev.duration_ns)))
            raw.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((ev.name, s, s + int(ev.duration_ns)))
                    if ev.name == CALL_SPAN:
                        window = (s, s + int(ev.duration_ns))
    devices = []
    for name, ops in sorted(raw):
        if window is None:
            lo = min((o.start for o in ops), default=0)
            hi = max((o.start + o.dur for o in ops), default=0)
            win = (lo, hi)
        else:
            win = window
        clipped = []
        for o in ops:
            s, e = max(o.start, win[0]), min(o.start + o.dur, win[1])
            if e > s:
                clipped.append(o._replace(start=s, dur=e - s))
        busy = _union_ns((o.start, o.start + o.dur) for o in clipped)
        devices.append(Device(name, clipped, busy, win))
    return Trace(devices, host)


# The program's named scopes (core/telemetry.stage_scope): the megakernel
# halves, the fused facility kernel, the fleet spill scan, and every engine
# stage (`stage_*`).
SCOPES = ("megakernel.demand", "megakernel.facility",
          "megakernel.facility.pallas", "fleet.spill_scan")


def _is_scope(part: str) -> bool:
    return part in SCOPES or part.startswith("stage_")


def innermost(path: str) -> str:
    """The innermost program scope that a scope path names, else 'other'."""
    for part in reversed(path.split("/")):
        if _is_scope(part):
            return part
    return "other"


def scope_ns(device: Device, scope: str) -> int:
    """Inclusive device time of `scope`: operations whose innermost scope
    is `scope` or lies inside it (`megakernel.facility` covers
    `megakernel.facility.pallas`; `megakernel.demand` covers the stages
    of the demand step)."""
    total = 0
    for o in device.ops:
        parts = o.path.split("/")
        if any(p == scope or p.startswith(scope + ".") for p in parts):
            total += o.dur
    return total


def window_ns(device: Device) -> int:
    return device.window[1] - device.window[0]


def top_ops(device: Device, n: int = 10):
    """[[name, seconds]] of the operations that took most device time,
    grouped by innermost scope and operation name."""
    acc = {}
    for o in device.ops:
        key = f"{innermost(o.path)}:{re.sub(r'[.]\d+$', '', o.name)}"
        acc[key] = acc.get(key, 0) + o.dur
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(device: Device, host, n: int = 10):
    """[[what the host was doing, seconds]] of the longest idle gaps: each
    gap is named after the shortest host event that covers its middle."""
    spans = sorted((o.start, o.start + o.dur) for o in device.ops)
    gaps, end = [], device.window[0]
    for s, e in spans:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if device.window[1] > end:
        gaps.append((end, device.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        covering = [(he - hs, name) for name, hs, he in host
                    if hs <= mid <= he and name != CALL_SPAN]
        label = min(covering)[1] if covering else "none"
        out.append([label, (e - s) / 1e9])
    return out


@contextlib.contextmanager
def named_scopes():
    """Turn on the program's stage scopes (telemetry.stage_scope names
    operations only while a telemetry session is active)."""
    from repro.core import telemetry
    tel = telemetry.enable(out_dir=os.devnull)
    try:
        yield tel
    finally:
        telemetry.disable()
