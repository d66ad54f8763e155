"""Observability layer: spans, run records, recompile detection, probe bus.

Everything here is **zero-overhead when disabled** (the default):

* :func:`span` / :func:`stage_scope` return ``nullcontext`` unless a
  :class:`Telemetry` session is active, so the engine's numerics are
  bitwise-identical with telemetry on or off — spans only measure host
  time and name operations, they never touch values.
* The per-step probe bus is opt-in via ``SimConfig.probes`` and lives in
  its own preallocated ring buffer threaded through the scan carry; with
  ``ProbeConfig.enabled = False`` the buffer is the ``None`` leafless
  pytree node and the step function is unchanged.

Host-side spans (:func:`span`) are exported as Chrome-trace JSON (loadable
in Perfetto or ``chrome://tracing``) and, for their duration, open a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows them on the host plane, on the device's clock.  Device work is named
by :func:`stage_scope`, a ``jax.named_scope`` around every engine stage,
the megakernel halves and the scheduler's parts: the names become the
``op_name`` metadata of the compiled module's instructions, which is how a
device trace's operations are put down to a stage.

Compile activity is observed through ``jax.monitoring``'s
``/jax/core/compile/*`` duration events: ``backend_compile_duration``
fires once per backend compile (including persistent-cache
deserialisation; in-memory jit cache hits fire none), which powers both
the compile-vs-steady-state split in :class:`RunRecord` and the
:func:`recompile_guard` detector that turns "this sweep recompiles per
cell" from a perf mystery into a test failure; ``jaxpr_trace_duration``
and ``jaxpr_to_mlir_module_duration`` time the tracing and lowering that
precede it (:class:`CompileWatch`).

Activate for a whole process with ``STEAM_TELEMETRY=1`` (output under
``STEAM_TELEMETRY_DIR``, default ``results/telemetry``), or locally::

    from repro.core import telemetry
    with telemetry.session() as tel:
        sweep_grid(...)
    # tel.export_chrome_trace() written on exit; run records in
    # results/telemetry/run_records.jsonl
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import threading
import time
import uuid
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class RecompileError(RuntimeError):
    """Raised by :func:`recompile_guard` under ``policy="raise"``."""


# ---------------------------------------------------------------------------
# Compile-event monitor (module-level; one listener for the whole process)
# ---------------------------------------------------------------------------

class _WallClock:
    """Seconds covered by intervals that are reported as they end (a
    jax.monitoring duration event fires at the end of its work).  An
    interval inside a later one counts once: an inner jit traced while an
    outer one traces fires its own event, whose time the outer one's
    already holds."""

    _KEEP = 64  # later intervals start after all but the last few ended

    def __init__(self) -> None:
        self.seconds = 0.0
        self._spans: list = []  # disjoint (start, end), ascending

    def add(self, duration: float) -> None:
        end = time.perf_counter()
        start = lo = end - duration
        covered = 0.0
        while self._spans and self._spans[-1][1] > start:
            s, e = self._spans.pop()
            covered += e - max(s, start)
            lo = min(lo, s)
        self.seconds += duration - covered
        self._spans.append((lo, end))
        del self._spans[:-self._KEEP]


class _CompileMonitor:
    """Accumulates backend-compile count and seconds, and the wall seconds
    spent tracing to a jaxpr and lowering to MLIR, from jax.monitoring."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.tracing = _WallClock()
        self.lowering = _WallClock()
        self._lock = threading.Lock()

    def on_event(self, event: str, duration: float, **kwargs: Any) -> None:
        with self._lock:
            if event == _COMPILE_EVENT:
                self.count += 1
                self.seconds += float(duration)
            elif event == _TRACE_EVENT:
                self.tracing.add(float(duration))
            elif event == _LOWER_EVENT:
                self.lowering.add(float(duration))


_MONITOR = _CompileMonitor()
_LISTENER_REGISTERED = False


def _ensure_listener() -> None:
    global _LISTENER_REGISTERED
    if _LISTENER_REGISTERED:
        return
    jax.monitoring.register_event_duration_secs_listener(_MONITOR.on_event)
    _LISTENER_REGISTERED = True


class CompileWatch:
    """Delta view over the compile monitor; see :func:`compile_watch`.

    ``count`` and ``seconds`` are backend compiles; ``trace_seconds`` and
    ``lower_seconds`` the wall time of the jaxpr tracing and MLIR lowering
    before them (a jit traced inside another counts once).
    ``since_start=True`` reads the process's totals since compile activity
    was first watched (the first :func:`compile_watch` or :func:`enable`)
    instead of counting from the watch's creation."""

    def __init__(self, since_start: bool = False) -> None:
        self._count0 = 0 if since_start else _MONITOR.count
        self._seconds0 = 0.0 if since_start else _MONITOR.seconds
        self._trace0 = 0.0 if since_start else _MONITOR.tracing.seconds
        self._lower0 = 0.0 if since_start else _MONITOR.lowering.seconds

    @property
    def count(self) -> int:
        return _MONITOR.count - self._count0

    @property
    def seconds(self) -> float:
        return _MONITOR.seconds - self._seconds0

    @property
    def trace_seconds(self) -> float:
        return _MONITOR.tracing.seconds - self._trace0

    @property
    def lower_seconds(self) -> float:
        return _MONITOR.lowering.seconds - self._lower0


@contextlib.contextmanager
def compile_watch():
    """Count backend compiles (and their seconds), and the seconds spent
    tracing and lowering, inside the block.

    Works standalone — no active telemetry session required — so the
    benchmarks can split compile time from steady-state throughput
    without enabling span capture.
    """
    _ensure_listener()
    yield CompileWatch()


class RecompileGuard:
    """Detects per-unit-of-work recompilation inside a block.

    Call :meth:`tick` after each unit (grid cell, chunk, bench rep).  A
    unit during which at least one backend compile fired counts as one
    *burst*; on exit, ``bursts > allowed`` triggers the policy
    (``"warn"`` → UserWarning, ``"raise"`` → :class:`RecompileError`,
    ``"ignore"`` → nothing).  Burst counting — rather than raw event
    counting — is robust to a single jit call emitting several compile
    events and to persistent-cache deserialisation showing up as a
    (cheap) compile.
    """

    def __init__(self, label: str, allowed: int = 1,
                 policy: str = "warn") -> None:
        if policy not in ("warn", "raise", "ignore"):
            raise ValueError(f"unknown recompile policy {policy!r}")
        self.label = label
        self.allowed = allowed
        self.policy = policy
        self.bursts = 0
        self.compiles = 0
        self._count0 = 0
        self._burst_mark = 0

    def __enter__(self) -> "RecompileGuard":
        _ensure_listener()
        self._count0 = _MONITOR.count
        self._burst_mark = _MONITOR.count
        self._ticked = False
        return self

    def mark(self) -> None:
        """Start a unit-of-work window: compiles before the next `tick`
        count toward a burst.  Use mark/tick pairs to exclude unrelated
        eager-op compiles (e.g. payload slicing) between units."""
        self._burst_mark = _MONITOR.count

    def tick(self) -> None:
        """Mark the end of one unit of work (cell / chunk / call)."""
        if _MONITOR.count > self._burst_mark:
            self.bursts += 1
        self._burst_mark = _MONITOR.count
        self._ticked = True

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._ticked:
            self.tick()  # plain-block usage: the whole block is one unit
        self.compiles = _MONITOR.count - self._count0
        if exc_type is not None:
            return
        if self.bursts > self.allowed:
            msg = (f"telemetry: {self.label!r} recompiled in {self.bursts} "
                   f"units of work (allowed {self.allowed}, "
                   f"{self.compiles} backend compiles total) — a sweep that "
                   f"recompiles per cell usually means a config field that "
                   f"should be static is varying, or vice versa")
            if self.policy == "raise":
                raise RecompileError(msg)
            if self.policy == "warn":
                warnings.warn(msg, UserWarning, stacklevel=2)


def recompile_guard(label: str, allowed: int = 1,
                    policy: Optional[str] = None) -> RecompileGuard:
    """Context manager: fail/warn when a block recompiles per unit of work.

    ``policy=None`` inherits the active session's ``recompile_policy``
    (default ``"warn"`` when no session is active).
    """
    if policy is None:
        tel = _ACTIVE
        policy = tel.recompile_policy if tel is not None else "warn"
    return RecompileGuard(label, allowed=allowed, policy=policy)


# ---------------------------------------------------------------------------
# Run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """One structured record per simulate/fleet/grid run (JSONL row)."""

    kind: str                       # "simulate" | "fleet" | "grid"
    run_id: str
    timestamp: str                  # ISO-8601 UTC
    config_hash: str
    backend: str                    # cfg.backend
    use_pallas: bool
    trace_store: str
    n_steps: int
    dt_h: float
    jax_backend: str
    device_count: int
    devices: list
    compile_time_s: float
    execute_time_s: float
    compiles: int
    pallas_interpret: Optional[bool] = None
    per_host_sum: Optional[str] = None   # "one_hot" | "segment_sum"
    first_fit: Optional[str] = None      # "pallas" | "while_loop"
    grid_shape: Optional[list] = None
    chunk: Optional[dict] = None    # chunk plan: predicted vs actual bytes
    mesh: Optional[dict] = None
    memory: Optional[list] = None   # per-device allocator watermarks
    trace_dtypes: Optional[dict] = None
    probes: Optional[dict] = None   # {"stride": ..., "capacity": ...}
    extra: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), default=str)

    @classmethod
    def from_json(cls, line: str) -> "RunRecord":
        return cls(**json.loads(line))


def config_hash(cfg: Any) -> str:
    """Stable short hash of a frozen-dataclass config (repr-based)."""
    return hashlib.sha1(repr(cfg).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Telemetry session
# ---------------------------------------------------------------------------

class Telemetry:
    """An active observability session: spans + run records + settings."""

    def __init__(self, out_dir: Optional[str] = None,
                 recompile_policy: str = "warn") -> None:
        self.out_dir = out_dir or os.environ.get(
            "STEAM_TELEMETRY_DIR", os.path.join("results", "telemetry"))
        self.recompile_policy = recompile_policy
        self.events: list = []          # Chrome-trace events
        self.records: list = []         # RunRecords emitted this session
        self.last_pallas_interpret: Optional[bool] = None
        self.last_per_host_sum: Optional[str] = None
        self.last_first_fit: Optional[str] = None
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    # -- spans ------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args: Any):
        """Host-side timed span, recorded as a Chrome-trace "X" event and,
        for its duration, as a profiler ``TraceAnnotation`` of the same
        name (a no-op unless a profiler trace is being captured)."""
        ts = self._now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dur = self._now_us() - ts
            ev = {"name": name, "ph": "X", "ts": ts, "dur": dur,
                  "pid": os.getpid(), "tid": threading.get_ident() % 100_000}
            if args:
                ev["args"] = {k: _json_safe(v) for k, v in args.items()}
            with self._lock:
                self.events.append(ev)

    def span_durations(self, name: str) -> list:
        """Total µs durations of all spans with the given name."""
        return [e["dur"] for e in self.events if e["name"] == name]

    def chrome_trace(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: Optional[str] = None) -> str:
        """Write the host-span Chrome trace JSON; returns the path."""
        path = path or os.path.join(self.out_dir, "trace.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    # -- run records ------------------------------------------------------
    def record(self, rec: RunRecord) -> RunRecord:
        with self._lock:
            self.records.append(rec)
        path = os.path.join(self.out_dir, "run_records.jsonl")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a") as f:
            f.write(rec.to_json() + "\n")
        return rec


def _json_safe(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


_ACTIVE: Optional[Telemetry] = None


def get() -> Optional[Telemetry]:
    """The active session, or None when telemetry is disabled."""
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


def enable(out_dir: Optional[str] = None,
           recompile_policy: str = "warn") -> Telemetry:
    """Activate a telemetry session (module-level singleton)."""
    global _ACTIVE
    _ensure_listener()
    _ACTIVE = Telemetry(out_dir=out_dir, recompile_policy=recompile_policy)
    return _ACTIVE


def disable() -> Optional[Telemetry]:
    """Deactivate; returns the session that was active (for inspection)."""
    global _ACTIVE
    tel, _ACTIVE = _ACTIVE, None
    return tel


@contextlib.contextmanager
def session(out_dir: Optional[str] = None, recompile_policy: str = "warn",
            export: bool = True):
    """``with telemetry.session() as tel: ...`` — enable, export, disable."""
    tel = enable(out_dir=out_dir, recompile_policy=recompile_policy)
    try:
        yield tel
    finally:
        if export and tel.events:
            tel.export_chrome_trace()
        disable()


def span(name: str, **args: Any):
    """Host span on the active session; nullcontext when disabled."""
    tel = _ACTIVE
    if tel is None:
        return contextlib.nullcontext()
    return tel.span(name, **args)


def stage_scope(name: str):
    """Name the operations traced inside the block after an engine stage,
    a kernel half or a part of one.

    A ``jax.named_scope``: it acts while Python traces the step, and its
    name becomes part of the ``op_name`` metadata of every instruction the
    block lowers to, which is what maps a device trace's operations to
    stages.  It records no event of its own, at trace time or at run time.
    Returns ``nullcontext`` when disabled, so tracing — and therefore the
    compiled computation — is untouched by default.
    """
    if _ACTIVE is None:
        return contextlib.nullcontext()
    return jax.named_scope(name)


def note_pallas_interpret(interpret: bool) -> None:
    """Record how the last Pallas call resolved (kernels/ops.py hook)."""
    tel = _ACTIVE
    if tel is not None:
        tel.last_pallas_interpret = bool(interpret)


def note_per_host_sum(form: str) -> None:
    """Record the form the last per-host sum took (core/scheduler.py hook)."""
    tel = _ACTIVE
    if tel is not None:
        tel.last_per_host_sum = form


def note_first_fit(form: str) -> None:
    """Record the form the last placement loop took (core/scheduler.py and
    kernels/ops.py hook)."""
    tel = _ACTIVE
    if tel is not None:
        tel.last_first_fit = form


def profile(fn, *args, logdir: Optional[str] = None, **kwargs):
    """One-command Perfetto capture: run ``fn`` under ``jax.profiler.trace``.

    Returns ``(result, logdir)``; load the written trace in Perfetto via
    ``xprof``/TensorBoard or convert with ``jax.profiler``'s tooling.
    """
    tel = _ACTIVE
    base = tel.out_dir if tel is not None else os.environ.get(
        "STEAM_TELEMETRY_DIR", os.path.join("results", "telemetry"))
    logdir = logdir or os.path.join(base, "profile")
    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    return out, logdir


# ---------------------------------------------------------------------------
# Run-record emission helper
# ---------------------------------------------------------------------------

def _utc_now_iso() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


class _RecordBuilder:
    """Mutable scratch a run wrapper fills in before the record is cut."""

    def __init__(self) -> None:
        self.grid_shape: Optional[list] = None
        self.chunk: Optional[dict] = None
        self.mesh: Optional[dict] = None
        self.trace_dtypes: Optional[dict] = None
        self.extra: dict = {}
        self.record: Optional[RunRecord] = None


@contextlib.contextmanager
def run_recorder(kind: str, cfg: Any, **extra: Any):
    """Wrap one run: times it, splits compile from execute, cuts a record.

    The caller must ensure the run's outputs are materialised (e.g.
    ``jax.block_until_ready``) before the block exits, otherwise the
    execute time only covers dispatch.
    """
    tel = _ACTIVE
    if tel is None:  # pragma: no cover - callers guard on enabled()
        yield _RecordBuilder()
        return
    builder = _RecordBuilder()
    builder.extra.update(extra)
    with compile_watch() as watch:
        t0 = time.perf_counter()
        with tel.span(kind, backend=getattr(cfg, "backend", None)):
            yield builder
        wall = time.perf_counter() - t0
    compile_s = min(watch.seconds, wall)
    pcfg = getattr(cfg, "probes", None)
    probes = None
    if pcfg is not None and pcfg.enabled:
        probes = {"stride": max(int(pcfg.stride), 1),
                  "capacity": probe_capacity(cfg.n_steps, pcfg)}
    interp = tel.last_pallas_interpret
    if interp is None and getattr(cfg, "use_pallas", False):
        try:
            from ..kernels.ops import resolved_interpret
            interp = bool(resolved_interpret())
        except Exception:  # pragma: no cover - kernels unavailable
            interp = None
    builder.record = tel.record(RunRecord(
        kind=kind,
        run_id=uuid.uuid4().hex[:12],
        timestamp=_utc_now_iso(),
        config_hash=config_hash(cfg),
        backend=getattr(cfg, "backend", "?"),
        use_pallas=bool(getattr(cfg, "use_pallas", False)),
        trace_store=getattr(cfg, "trace_store", "?"),
        n_steps=int(getattr(cfg, "n_steps", 0)),
        dt_h=float(getattr(cfg, "dt_h", 0.0)),
        jax_backend=jax.default_backend(),
        device_count=jax.device_count(),
        devices=[str(d) for d in jax.devices()],
        compile_time_s=compile_s,
        execute_time_s=max(wall - compile_s, 0.0),
        compiles=watch.count,
        pallas_interpret=interp,
        per_host_sum=tel.last_per_host_sum,
        first_fit=tel.last_first_fit,
        memory=device_memory_watermarks(),
        grid_shape=builder.grid_shape,
        chunk=builder.chunk,
        mesh=builder.mesh,
        trace_dtypes=builder.trace_dtypes,
        probes=probes,
        extra=builder.extra,
    ))


def device_memory_watermarks() -> list:
    """Per-device allocator stats from the PJRT client (the backing store of
    ``jax.profiler``'s device-memory view).  Each entry reports
    ``peak_bytes_in_use`` / ``bytes_in_use`` or ``None`` where the platform
    exposes no allocator stats (the CPU backend): absence is data —
    downstream tables print it next to the *predicted* chunk-plan bytes so
    a reader can tell "no watermark available" from "zero bytes"."""
    out = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # pragma: no cover - backend without memory_stats
            stats = {}
        out.append({"device": str(d),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_in_use": stats.get("bytes_in_use")})
    return out


def peak_bytes_per_device() -> Optional[int]:
    """Max ``peak_bytes_in_use`` across local devices, or None (CPU)."""
    peaks = [m["peak_bytes_in_use"] for m in device_memory_watermarks()
             if m["peak_bytes_in_use"] is not None]
    return max(peaks) if peaks else None


def is_tracing(tree: Any) -> bool:
    """True when any leaf is a JAX tracer (run is inside jit/vmap/scan)."""
    return any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Per-step probe bus
# ---------------------------------------------------------------------------

class Probes(NamedTuple):
    """Strided ring-buffer samples captured inside the scan.

    All fields are ``[K]`` arrays (``K`` = :func:`probe_capacity`); rows
    whose ``step`` is ``-1`` were never written (horizon shorter than
    the buffer).  Fields mirror the settled :class:`~.engine.EnergyFlow`
    ledger for the step, plus battery state of charge (post-dispatch),
    the intra-billing-window running peak (post-pricing), the scheduler
    queue depth (tasks arrived but still pending), and the resilience
    series: the thermal throttle the step ran under, the chiller derate
    and the PDU power cap in force (1.0 / 1.0 / +inf whenever
    ``cfg.resilience`` is off — the channels exist on both backends
    regardless, so probe consumers never branch on the config).
    """

    step: jax.Array             # i32[K]: sim step index of the sample
    it_kw: jax.Array
    cooling_kw: jax.Array
    pv_kw: jax.Array
    batt_charge_kw: jax.Array
    batt_discharge_kw: jax.Array
    grid_import_kw: jax.Array
    grid_export_kw: jax.Array
    curtailed_kw: jax.Array
    soc_kwh: jax.Array          # battery charge after dispatch
    window_peak_kw: jax.Array   # running intra-window demand peak
    queue_depth: jax.Array      # arrived-but-pending tasks
    throttle_factor: jax.Array  # thermal throttle APPLIED this step (1 = none)
    chiller_derate: jax.Array   # facility-failure cooling derate (1 = healthy)
    pdu_cap_kw: jax.Array       # rack-power clamp in force (+inf = healthy)


PROBE_VALUE_FIELDS = tuple(f for f in Probes._fields if f != "step")


def probe_capacity(n_steps: int, pcfg: Any) -> int:
    """Ring-buffer length: all strided samples, capped at max_samples."""
    stride = max(int(pcfg.stride), 1)
    total = -(-int(n_steps) // stride)
    if pcfg.max_samples and pcfg.max_samples > 0:
        return min(int(pcfg.max_samples), total)
    return total


def init_probes(n_steps: int, pcfg: Any) -> Probes:
    """Preallocate the ring buffer carried through the scan."""
    k = probe_capacity(n_steps, pcfg)
    z = jnp.zeros((k,), jnp.float32)
    return Probes(step=jnp.full((k,), -1, jnp.int32),
                  **{f: z for f in PROBE_VALUE_FIELDS})


def probe_write(buf: Probes, step: jax.Array, stride: int,
                values: dict) -> Probes:
    """Conditionally write one sample; used by the engine's probe stage.

    ``step`` is the pre-increment step index of the state being
    sampled.  Rows wrap modulo the capacity, so a capped buffer keeps
    the **last** ``K`` strided samples.
    """
    k = buf.step.shape[0]
    take = (step % stride) == 0
    row = (step // stride) % k

    def write(arr, v):
        v = jnp.asarray(v, arr.dtype)
        return arr.at[row].set(jnp.where(take, v, arr[row]))

    return Probes(step=write(buf.step, step),
                  **{f: write(getattr(buf, f), values[f])
                     for f in PROBE_VALUE_FIELDS})


def probes_from_series(n_steps: int, pcfg: Any, series: dict) -> Probes:
    """Assemble the identical ring buffer from full per-step series.

    The megakernel backend computes facility physics vectorised over the
    horizon rather than inside the scan; this gathers the same strided
    rows (including ring wrap-around: row ``j`` holds the *last* sample
    whose index ≡ j mod K) so both backends export bitwise-compatible
    probes.
    """
    stride = max(int(pcfg.stride), 1)
    k = probe_capacity(n_steps, pcfg)
    total = -(-int(n_steps) // stride)
    # last sample index landing on ring row j: j + floor((total-1-j)/K)*K
    sample_idx = [j + ((total - 1 - j) // k) * k for j in range(k)]
    steps = jnp.asarray([s * stride for s in sample_idx], jnp.int32)
    return Probes(step=steps,
                  **{f: jnp.asarray(series[f], jnp.float32)[steps]
                     for f in PROBE_VALUE_FIELDS})


def window_peak_series(grid_kw: jax.Array, window_steps: int) -> jax.Array:
    """Running intra-billing-window peak at every step, vectorised.

    Matches ``pricing.pricing_step`` semantics exactly: the window
    resets at steps ``k*W`` (k>0) *before* absorbing that step's demand,
    so the peak at step t covers ``grid_kw[(t//W)*W : t+1]`` — a
    per-window cummax after padding to a multiple of W.
    """
    s = grid_kw.shape[0]
    w = max(int(window_steps), 1)
    n_win = -(-s // w)
    pad = n_win * w - s
    padded = jnp.concatenate(
        [grid_kw, jnp.zeros((pad,), grid_kw.dtype)]) if pad else grid_kw
    return jax.lax.cummax(padded.reshape(n_win, w), axis=1).reshape(-1)[:s]


# Activate from the environment (used by CI bench-smoke).
if os.environ.get("STEAM_TELEMETRY", "") not in ("", "0"):
    enable()
