"""Device time per program scope: a trace's operations named by the
compiled module (perfbench/scopes.py), and the readers of this layer's
metrics (perfbench/program_view.py)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import manifest, program_view, scopes, trace  # noqa: E402

SCOPE_READERS = ("demand_ms", "scheduler_ms", "first_fit_ms", "facility_ms")
COUNTER_READERS = ("first_fit_iters_per_step", "first_fit_yield_pct")


def _device(ops, window=(0, 100)):
    ops = [trace.Op(n, p, s, d) for n, p, s, d in ops]
    busy = trace._union_ns((o.start, o.start + o.dur) for o in ops)
    return trace.Device("/device:TPU:0", ops, busy, window)


def _run(devices, n_steps=96):
    return manifest.RunData(trace.Trace(devices, []), 0.1, None,
                            "TPU v5 lite", 1, n_steps)


@pytest.fixture
def no_view(monkeypatch):
    """A process with no view yet, whose command line names no cell."""
    monkeypatch.setattr(sys, "argv", ["pytest"])
    monkeypatch.setattr(program_view, "_VIEW", None)


def test_a_while_and_its_body_count_once():
    scan = "jit(run)/megakernel.demand/while"
    body = scan + "/body/closed_call/stage_scheduler"
    dev = _device([
        ("while.1", scan, 0, 60),                     # the scan, 0 .. 60
        ("fusion.1", body + "/stage_scheduler.candidates", 2, 10),
        ("while.2", body + "/stage_scheduler.first_fit/while", 15, 20),
        ("fusion.2", body + "/stage_scheduler.first_fit/while/body", 16, 5),
        ("fusion.3", scan + "/body/closed_call/stage_progress", 40, 10),
        ("fusion.4", "jit(run)/megakernel.facility/jit(f)/"
                     "megakernel.facility.pallas/pallas_call", 70, 8),
        ("copy.1", "", 90, 2)])
    assert scopes.scope_ns(dev, "megakernel.demand") == 60   # not 105
    assert scopes.scope_ns(dev, "stage_scheduler") == 30     # 2..12, 15..35
    assert scopes.scope_ns(dev, "stage_scheduler.first_fit") == 20
    assert scopes.scope_ns(dev, "megakernel.facility") == 8
    assert scopes.scope_ns(dev, "stage_power") is None
    assert scopes.coverage(dev) == pytest.approx(68 / 70)
    # the sum the old rule gave would count the scan's body twice
    assert trace.scope_ns(dev, "megakernel.demand") == 105


def test_the_compiled_module_names_each_instruction():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("outer.part"):
            y = jnp.cumsum(x) * 2.0
        return jax.lax.while_loop(lambda c: c[0] < 3,
                                  lambda c: (c[0] + 1, c[1] + y),
                                  (0, y))[1]

    text = jax.jit(f).lower(jnp.arange(8.0)).compile().as_text()
    module, names = scopes.op_names(text)
    assert module == "jit_f"
    assert any(p.startswith("jit(f)/outer.part/") for p in names.values())
    assert "jit(f)/while" in {p for n, p in names.items()
                              if n.startswith("while")}


def test_a_scope_under_a_transformation_is_read_as_itself():
    text = ('HloModule jit_run\n'
            '  %while.9 = (s32[]) while(%t), body=%b, metadata={op_name='
            '"jit(run)/vmap(vmap(megakernel.demand))/while"}\n'
            '  %fusion.2 = f32[2]{0} fusion(), metadata={op_name='
            '"jit(run)/vmap(jit(f))/add"}\n')
    _, names = scopes.op_names(text)
    assert names == {"while.9": "jit(run)/megakernel.demand/while",
                     "fusion.2": "jit(run)/vmap(jit(f))/add"}


def test_attribution_keeps_a_trace_scope_and_rejects_another_module():
    text = ('HloModule jit_run, is_scheduled=true\n'
            '  %fusion.7 = f32[4]{0} fusion(), kind=kLoop, calls=%c.1, '
            'metadata={op_name="jit(run)/megakernel.demand/add"}\n'
            '  ROOT %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
            'metadata={op_name="jit(run)/megakernel.demand/while" '
            'stack_frame_id=4}, backend_config={"n":"1"}\n'
            '  %copy.2 = f32[4]{0} copy(%p)\n')
    dev = _device([("fusion.7", "", 0, 5), ("while.3", "", 0, 10),
                   ("copy.2", "", 10, 1), ("bitcast.1", "tf/scope", 11, 1)])
    tr = trace.Trace([dev], [])
    # copy.2 has no metadata, bitcast.1 is no instruction of the module
    assert scopes.attribute(tr, text) == pytest.approx(3 / 4)
    assert [o.path for o in dev.ops] == [
        "jit(run)/megakernel.demand/add", "jit(run)/megakernel.demand/while",
        "", "tf/scope"]
    other = trace.Trace([_device([("add.1", "", 0, 1)])], [])
    with pytest.raises(ValueError, match="another program"):
        scopes.attribute(other, text)


def test_scope_readers_read_union_time_in_ms(no_view):
    dev = _device([
        ("while.1", "jit(run)/megakernel.demand/while", 0, 6_000_000),
        ("while.2", "jit(run)/megakernel.demand/while/body/stage_scheduler/"
                    "stage_scheduler.first_fit/while", 1_000_000, 2_000_000),
        ("fusion.1", "jit(run)/megakernel.facility/x", 6_000_000, 500_000)],
        (0, 7_000_000))
    run = _run([dev])
    got = {n: manifest.reader(n, ROOT).read(run) for n in SCOPE_READERS}
    assert got == {"demand_ms": 6.0, "scheduler_ms": 2.0,
                   "first_fit_ms": 2.0, "facility_ms": 0.5}


def test_new_readers_read_nothing_without_a_device_or_a_cell(no_view):
    run = _run([])
    for name in SCOPE_READERS + COUNTER_READERS + ("trace_lower_s",):
        assert manifest.reader(name, ROOT).read(run) is None, name


def test_counter_readers_read_the_rebuilt_program(no_view):
    """At a test's size on the CPU: the view rebuilds the cell's program,
    calls it, and the readers divide its counters as documented."""
    import jax
    import jax.numpy as jnp
    from repro.core import telemetry

    from perfbench.record_trace import tiny_cell
    with telemetry.compile_watch():  # set-up traces under a watch
        jax.jit(lambda x: x * 3.5)(jnp.arange(5.0)).block_until_ready()
    view = program_view.build(tiny_cell(), 3)
    iters = float(view.first_fit_iters.sum())
    placed = float(view.first_fit_placed.sum())
    assert iters >= placed > 0
    run = _run([], n_steps=96)
    per_step = manifest.reader("first_fit_iters_per_step", ROOT).read(run)
    assert per_step == pytest.approx(iters / 96)
    assert manifest.reader("first_fit_yield_pct", ROOT).read(run) == \
        pytest.approx(100 * placed / iters)
    assert manifest.reader("trace_lower_s", ROOT).read(run) > 0


# One traced call of `simulate` over SURF cut to 8 hosts and 1 day, and the
# compiled module it ran, recorded on a TPU v5e by
# perfbench/record_scoped_trace.py.
SCOPED = ROOT / "perfbench" / "tests" / "surf_tiny_scoped"


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(f"{SCOPED}.xplane.pb.gz")
    text = scopes.read_text(f"{SCOPED}.hlo.txt.gz")
    return tr, text


def test_the_recorded_trace_ran_the_recorded_module(recorded):
    tr, text = recorded
    module, _ = scopes.op_names(text)
    assert scopes.module_names(f"{SCOPED}.xplane.pb.gz") == [module]
    assert all(trace.innermost(o.path) == "other"
               for d in tr.devices for o in d.ops)


def test_the_recorded_trace_maps_to_nested_scopes(recorded):
    tr, text = recorded
    assert scopes.attribute(tr, text) == 1.0
    dev = tr.devices[0]
    # 99.59% when recorded: copies the compiler adds carry no scope
    assert scopes.coverage(dev) >= 0.995
    demand = scopes.scope_ns(dev, "megakernel.demand")
    sched = scopes.scope_ns(dev, "stage_scheduler")
    first_fit = scopes.scope_ns(dev, "stage_scheduler.first_fit")
    facility = scopes.scope_ns(dev, "megakernel.facility")
    assert 0 < first_fit <= sched <= demand <= dev.busy_ns
    # the facility chain runs after the scan: disjoint from it
    both = trace._union_ns(
        (o.start, o.start + o.dur) for o in dev.ops
        if scopes._under(o.path, "megakernel.demand")
        or scopes._under(o.path, "megakernel.facility"))
    assert both == demand + facility
    assert not any(trace.innermost(o.path) == "other"
                   for o in dev.ops if o.name.startswith("while"))
