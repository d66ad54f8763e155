"""The main path compiles for a TPU v5e, at deployment widths.

Every other kernel test runs the Pallas interpreter on the CPU, which
accepts code the chip's compiler refuses (scalar stores to VMEM, dynamic
lane reads, unaligned blocks).  Here the TPU compiler installed with JAX
compiles each kernel for a described `v5e:2x2` topology, with no chip
attached: the widths are the SURF-calibrated datacenter (277 hosts, 124 days
= 11,904 steps at 15 min), the Borg one (1,534 hosts: two host tiles) and
an 8-region fleet.  The scheduler's per-host sums are compiled too, at
the SURF task count: there they must be one contraction each, with no
scatter; the progress stage, which must gather no task-wide column from
the host table; and the placement, which must be one kernel launch a step
at the SURF, Marconi (972 hosts) and Borg widths.  At Borg's full size
(1,131,689 task rows, 4,096 slots a step) the placement, the commit (the
placed rows written by index, nothing gathered per task row) and the
per-host sums (no `[H, T]` buffer) are compiled too.  Nothing runs, so
results are checked elsewhere.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (BatteryConfig, CoolingConfig, PricingConfig,
                        RenewableConfig, SchedulerConfig, SimConfig, device,
                        engine, init_sim_state, make_host_table,
                        make_task_table, scheduler, telemetry)
from repro.core.config import PowerModelConfig
from repro.kernels import ops

SURF_HOSTS = 277
MARCONI_HOSTS = 972
BORG_HOSTS = 1534
SURF_STEPS = 11904          # 124 days at dt = 0.25 h
SURF_TASKS = 93587          # 14 days of the SURF arrival density
BORG_TASKS = 1131689        # 7 days of the Borg arrival density
BORG_SLOTS = 4096           # the Borg cell's placement bound
REGIONS = 8

CPU = PowerModelConfig(80.0, 250.0, "sqrt")
GPU = PowerModelConfig(40.0, 300.0, "linear")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device to compile for, with the persistent cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the code read the platform it runs on as the TPU: Pallas
    kernels compile for Mosaic, and the per-host sums take the TPU's form
    (both would follow this CPU backend otherwise)."""
    monkeypatch.setattr(device, "call_platform", lambda: "tpu")


@pytest.fixture
def compiled(on_tpu):
    """Compile `fn` for the chip through the ops layer, kernels compiled."""

    def compile_(fn, *shapes):
        exe = jax.jit(fn).lower(*shapes).compile()
        assert "tpu_custom_call" in exe.as_text()
        return exe
    return compile_


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("h", [SURF_HOSTS, BORG_HOSTS])
def test_power_carbon_compiles(one_chip, compiled, h):
    compiled(lambda cu, gu, ng, on, ci, dt: ops.fused_power_carbon(
        cu, gu, ng, on, ci, dt, CPU, GPU),
        *[_f32(one_chip, h)] * 4, _f32(one_chip), _f32(one_chip))


@pytest.mark.parametrize("h", [SURF_HOSTS, BORG_HOSTS])
def test_facility_power_compiles(one_chip, compiled, h):
    compiled(lambda cu, gu, ng, on, wb, sp: ops.facility_power(
        cu, gu, ng, on, wb, sp, CPU, GPU, CoolingConfig(enabled=True)),
        *[_f32(one_chip, h)] * 4, _f32(one_chip), _f32(one_chip))


def test_facility_power_batched_compiles(one_chip, compiled):
    compiled(lambda cu, gu, ng, on, wb, sp: ops.facility_power_batched(
        cu, gu, ng, on, wb, sp, CPU, GPU, CoolingConfig(enabled=True)),
        *[_f32(one_chip, REGIONS, SURF_HOSTS)] * 4,
        _f32(one_chip, REGIONS), _f32(one_chip, REGIONS))


@pytest.mark.parametrize("store", ["bf16", "int8"])
def test_facility_totals_compiles(one_chip, compiled, store):
    """The megakernel's facility chain with every technique composed."""
    cfg = SimConfig(
        n_steps=SURF_STEPS,
        cooling=CoolingConfig(enabled=True, heat_reuse_fraction=0.3),
        pricing=PricingConfig(enabled=True, billing_window_h=24.0),
        renewables=RenewableConfig(enabled=True, pv_capacity_kw=40.0),
        battery=BatteryConfig(enabled=True, capacity_kwh=100.0))
    series = [_f32(one_chip, SURF_STEPS)] * 8
    rising = jax.ShapeDtypeStruct((SURF_STEPS,), jnp.bool_, sharding=one_chip)
    compiled(lambda *xs: ops.facility_totals(*xs, cfg, trace_store=store),
             *series, rising)


def _table_shapes(table, n, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,), x.dtype, sharding=sharding),
        table)


@pytest.mark.parametrize("fn", [scheduler.free_capacity,
                                scheduler.host_utilization])
@pytest.mark.parametrize("h", [SURF_HOSTS, BORG_HOSTS])
def test_per_host_sums_compile_to_one_contraction(one_chip, on_tpu, fn, h):
    """On the TPU a per-host sum is one contraction of the host one-hot
    with the stacked columns, at every host count (a scatter-add there
    serializes over the task rows), and the one-hot stays in the fusion:
    no buffer as large as the task table is written."""
    tasks = _table_shapes(make_task_table([0.0], [1.0], [1.0]), SURF_TASKS,
                          one_chip)
    hosts = _table_shapes(make_host_table(1, 16), h, one_chip)
    assert scheduler.per_host_sum_form(h) == "one_hot"
    exe = jax.jit(fn).lower(tasks, hosts).compile()
    text = exe.as_text()
    assert not re.findall(r"\bscatter\(", text)
    assert len(re.findall(r"\b(?:convolution|dot)\(", text)) == 1
    assert exe.memory_analysis().temp_size_in_bytes < 4 * SURF_TASKS


@pytest.mark.parametrize("h", [SURF_HOSTS, BORG_HOSTS])
def test_progress_gathers_no_task_rows_from_the_host_table(one_chip, on_tpu,
                                                           h):
    """The progress stage reads each running task's host speed from the
    task table, where placement wrote it: no gather as wide as the task
    table (XLA's general gather emitter, one host-table read per row)."""
    state = init_sim_state(make_task_table([0.0], [1.0], [1.0]),
                           make_host_table(1, 16))
    state = state._replace(
        tasks=_table_shapes(state.tasks, SURF_TASKS, one_chip),
        hosts=_table_shapes(state.hosts, h, one_chip))
    state = jax.tree.map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct) else
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), state)
    step = engine.stage_progress(SimConfig())
    text = jax.jit(lambda s: step(s, {})[0].tasks).lower(
        state).compile().as_text()
    assert not re.findall(rf"= \w+\[{SURF_TASKS}\]\S* gather\(", text)


def _placement_text(one_chip, tmp_path, h, t, lanes=None, k=64):
    """The compiled text of one scheduler step (k slots, t task rows, h
    hosts), scopes named; `lanes` batches every input by `vmap`."""
    tasks = _table_shapes(make_task_table([0.0], [1.0], [1.0]), t, one_chip)
    hosts = _table_shapes(make_host_table(1, 16), h, one_chip)
    ok = jax.ShapeDtypeStruct((t,), jnp.bool_, sharding=one_chip)
    now = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    cfg = SchedulerConfig(slots_per_step=k)
    fn = lambda *a: scheduler.schedule_first_fit(*a, cfg)
    args = (tasks, hosts, now, ok)
    if lanes is not None:
        fn = jax.vmap(fn)
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((lanes, *x.shape), x.dtype,
                                           sharding=one_chip), args)
    with telemetry.session(out_dir=str(tmp_path), export=False):
        return jax.jit(fn).lower(*args).compile().as_text()


def _scope_lines(text, scope):
    """Instructions named under `scope`, bare or wrapped by a transformation
    (`vmap(stage_scheduler.first_fit)`)."""
    return [line for line in text.splitlines()
            if re.search(rf'op_name="[^"]*[/(]{re.escape(scope)}[)/]', line)]


@pytest.mark.parametrize("h", [SURF_HOSTS, MARCONI_HOSTS, BORG_HOSTS])
def test_first_fit_compiles_to_one_kernel(one_chip, on_tpu, tmp_path, h):
    """On the TPU the placement loop is one Pallas kernel a step: the
    `stage_scheduler.first_fit` scope holds a Mosaic call and no `while`,
    at every deployment width, 64 slots a step."""
    assert scheduler.first_fit_form() == "pallas"
    lines = _scope_lines(_placement_text(one_chip, tmp_path, h, SURF_TASKS),
                         "stage_scheduler.first_fit")
    assert any('custom_call_target="tpu_custom_call"' in x for x in lines)
    assert not any(re.search(r"\bwhile\(", x) for x in lines)


def test_batched_first_fit_compiles_on_the_loop(one_chip, on_tpu, tmp_path):
    """Placement batched over 8 lanes keeps the `while_loop` (the door's
    batching rule) and compiles for the chip."""
    lines = _scope_lines(
        _placement_text(one_chip, tmp_path, SURF_HOSTS, 4096, lanes=8),
        "stage_scheduler.first_fit")
    assert any(re.search(r"\bwhile\(", x) for x in lines)
    assert not any("tpu_custom_call" in x for x in lines)


def test_borg_placement_and_commit_compile_at_published_width(
        one_chip, on_tpu, tmp_path):
    """At Borg's width (1,534 hosts, 1,131,689 task rows, 4,096 slots a
    step) placement is still one kernel launch a step, and the commit
    writes the placed rows by index: no gather of task rows from the
    k-entry result and no select chain over k slot masks, nothing
    task-wide but the four scattered columns."""
    text = _placement_text(one_chip, tmp_path, BORG_HOSTS, BORG_TASKS,
                           k=BORG_SLOTS)
    first_fit = _scope_lines(text, "stage_scheduler.first_fit")
    assert sum('custom_call_target="tpu_custom_call"' in x
               for x in first_fit) == 1
    assert not any(re.search(r"\bwhile\(", x) for x in first_fit)
    commit = _scope_lines(text, "stage_scheduler.commit")
    task_wide = rf"= \w+\[{BORG_TASKS}\]\S* (\w[\w-]*)\("
    ops = {m.group(1) for x in commit for m in [re.search(task_wide, x)] if m}
    assert not ops & {"gather", "select"}, ops
    assert sum(bool(re.search(r"\bselect\(", x)) for x in commit) < 8
    assert sum(bool(re.search(r"\bscatter\(", x)) for x in commit) == 4


@pytest.mark.parametrize("fn", [scheduler.free_capacity,
                                scheduler.host_utilization])
def test_borg_per_host_sums_hold_no_host_by_task_buffer(one_chip, on_tpu,
                                                         fn):
    """The per-host sums at Borg's 1,534 hosts x 1,131,689 task rows: one
    contraction, its one-hot never written (an `[H, T]` buffer would take
    6.9 GB as f32)."""
    tasks = _table_shapes(make_task_table([0.0], [1.0], [1.0]), BORG_TASKS,
                          one_chip)
    hosts = _table_shapes(make_host_table(1, 16), BORG_HOSTS, one_chip)
    exe = jax.jit(fn).lower(tasks, hosts).compile()
    assert len(re.findall(r"\b(?:convolution|dot)\(", exe.as_text())) == 1
    assert exe.memory_analysis().temp_size_in_bytes < 4 * BORG_TASKS
