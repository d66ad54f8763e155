"""Property-based tests (hypothesis) for engine invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dependency: property-based tier")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (BatteryConfig, DONE, FailureConfig, INVALID,
                        ShiftingConfig, SimConfig, simulate, summarize,
                        make_host_table, make_task_table)
from repro.carbontraces import make_region_traces, trace_stats

N_STEPS = 24 * 4 * 3  # 3 days


def _workload(rng_seed, n_tasks, max_cores):
    rng = np.random.default_rng(rng_seed)
    arrival = np.sort(rng.uniform(0.0, 36.0, n_tasks))
    duration = rng.uniform(0.25, 8.0, n_tasks)
    cores = rng.integers(1, max_cores + 1, n_tasks).astype(float)
    return make_task_table(arrival, duration, cores)


@st.composite
def scenario(draw):
    return dict(
        seed=draw(st.integers(0, 2**16)),
        n_tasks=draw(st.integers(1, 40)),
        n_hosts=draw(st.integers(1, 6)),
        cores=draw(st.sampled_from([2, 4, 8])),
        battery=draw(st.booleans()),
        shifting=draw(st.booleans()),
        failures=draw(st.booleans()),
        ci_level=draw(st.floats(10.0, 800.0)),
        ci_swing=draw(st.floats(0.0, 0.9)),
    )


def _run(s):
    tasks = _workload(s["seed"], s["n_tasks"], max_cores=s["cores"])
    hosts = make_host_table(s["n_hosts"], s["cores"])
    t = np.arange(N_STEPS) * 0.25
    trace = s["ci_level"] * (1 + s["ci_swing"] * np.sin(2 * np.pi * t / 24.0))
    cfg = SimConfig(
        n_steps=N_STEPS,
        battery=BatteryConfig(enabled=s["battery"], capacity_kwh=5.0),
        shifting=ShiftingConfig(enabled=s["shifting"]),
        failures=FailureConfig(enabled=s["failures"], mtbf_h=50.0),
        collect_series=True,
    )
    final, series = jax.jit(
        lambda tr: simulate(tasks, hosts, tr, cfg))(jnp.asarray(trace, jnp.float32))
    return summarize(final, cfg), final, series, cfg


@settings(max_examples=25, deadline=None)
@given(scenario())
def test_invariants_hold_for_random_scenarios(s):
    res, final, series, cfg = _run(s)
    # all metrics finite and sane
    for name, v in res._asdict().items():
        if v is None:
            continue  # probes: off by default
        assert np.isfinite(float(v)), name
    assert 0.0 <= float(res.sla_violation_frac) <= 1.0
    assert 0.0 <= float(res.done_frac) <= 1.0
    assert float(res.op_carbon_kg) >= 0 and float(res.emb_carbon_kg) >= 0
    assert float(res.grid_energy_kwh) >= -1e-4
    # capacity invariant: no host ever over-committed
    assert float(jnp.max(series["max_overcommit"])) <= 1e-4
    # battery bounds
    charge = np.asarray(series["battery_charge"])
    assert np.all(charge >= -1e-4) and np.all(charge <= 5.0 + 1e-4)
    # grid power never negative
    assert float(jnp.min(series["grid_power_kw"])) >= -1e-4
    # status codes legal
    status = np.asarray(final.tasks.status)
    assert np.all((status >= 0) & (status <= INVALID))
    # done tasks have consistent finish times
    done = status == DONE
    fin = np.asarray(final.tasks.finish)[done]
    arr = np.asarray(final.tasks.arrival)[done]
    dur = np.asarray(final.tasks.duration)[done]
    assert np.all(fin >= arr + dur - 0.26)   # can't finish faster than duration
    # peak power >= average power
    avg = float(res.grid_energy_kwh) / (N_STEPS * 0.25)
    assert float(res.peak_power_kw) >= avg - 1e-5


@settings(max_examples=10, deadline=None)
@given(scenario())
def test_energy_balance(s):
    """grid_energy = dc_energy + battery_charged - battery_discharged."""
    res, final, series, cfg = _run(s)
    grid = float(res.grid_energy_kwh)
    dc = float(res.dc_energy_kwh)
    if not s["battery"]:
        assert abs(grid - dc) < 1e-3
    else:
        # net grid surplus went into the battery (minus efficiency loss) or
        # came out of it; surplus must be >= -discharged
        assert grid - dc >= -float(res.batt_discharged_kwh) - 1e-3


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_shifting_never_increases_decided_work(seed):
    """Shifting may delay but must not lose tasks relative to baseline."""
    s = dict(seed=seed, n_tasks=24, n_hosts=3, cores=4, battery=False,
             shifting=False, failures=False, ci_level=300.0, ci_swing=0.5)
    base, bf, _, _ = _run(s)
    s2 = dict(s, shifting=True)
    shift, sf, _, _ = _run(s2)
    # within the same horizon shifting can leave late tasks unfinished, but
    # every task that was decided must still eventually run: done + pending
    # equals total in both runs
    assert int(float(base.n_tasks)) == int(float(shift.n_tasks))
    assert float(shift.mean_start_delay_h) >= float(base.mean_start_delay_h) - 1e-5


def test_carbon_trace_population_matches_paper():
    traces = make_region_traces(24 * 4 * 30, n_regions=158, seed=0)
    mean, var = trace_stats(traces)
    assert traces.shape == (158, 24 * 4 * 30)
    assert np.all(traces > 0)
    assert mean.min() >= 10.0 and mean.max() <= 1000.0
    # population spans the paper's Fig 13 ranges
    assert mean.min() < 40.0 and mean.max() > 500.0
    assert var.max() > 0.3 and var.min() < 0.1


# ---------------------------------------------------------------------------
# single-pass priority scheduler: differential properties (ISSUE 10)
# ---------------------------------------------------------------------------

from repro.core import RUNNING, SchedulerConfig  # noqa: E402
from repro.core.scheduler import (_first_k_by_priority,  # noqa: E402
                                  _first_k_by_priority_reference,
                                  schedule_first_fit)
from repro.core.state import (inverse_permutation,  # noqa: E402
                              permute_task_table, priority_schedule_order)


@st.composite
def priority_select_case(draw):
    n = draw(st.integers(1, 96))
    levels = draw(st.integers(1, 5))
    seed = draw(st.integers(0, 2**16))
    rng = np.random.default_rng(seed)
    return dict(
        mask=rng.uniform(size=n) < draw(st.floats(0.0, 1.0)),
        # include out-of-range codes: they match no level and never select
        prio=rng.integers(-1, levels + 1, n),
        k=draw(st.integers(1, 2 * n)),
        levels=levels,
    )


@settings(max_examples=50, deadline=None)
@given(priority_select_case())
def test_single_pass_select_matches_per_level_reference(c):
    """The one-cumsum `[L*T]` select is the per-level oracle, bit for bit."""
    mask = jnp.asarray(c["mask"])
    prio = jnp.asarray(c["prio"], jnp.int32)
    got = np.asarray(_first_k_by_priority(mask, prio, c["k"], c["levels"]))
    ref = np.asarray(_first_k_by_priority_reference(
        mask, prio, c["k"], c["levels"]))
    np.testing.assert_array_equal(got, ref)
    # and both match the numpy lexsort model on in-range rows
    idx = np.nonzero(c["mask"] & (c["prio"] >= 0)
                     & (c["prio"] < c["levels"]))[0]
    order = idx[np.lexsort((idx, -c["prio"][idx]))][:c["k"]]
    expect = np.full(c["k"], -1, np.int64)
    expect[:order.shape[0]] = order
    np.testing.assert_array_equal(got, expect)


@st.composite
def admission_case(draw):
    seed = draw(st.integers(0, 2**16))
    n = draw(st.integers(1, 48))
    levels = draw(st.integers(2, 4))
    rng = np.random.default_rng(seed)
    arrival = np.sort(rng.uniform(0.0, 12.0, n))
    duration = rng.uniform(0.5, 6.0, n)
    cores = rng.integers(1, 4, n).astype(float)
    prio = rng.integers(0, levels, n)
    return dict(arrival=arrival, duration=duration, cores=cores,
                prio=prio, levels=levels,
                k=draw(st.integers(1, 16)),
                n_hosts=draw(st.integers(1, 3)),
                host_cores=draw(st.sampled_from([2, 4])),
                now=draw(st.floats(0.0, 14.0)))


def _admission_tables(c):
    tasks = make_task_table(c["arrival"], c["duration"], c["cores"],
                            priority=np.asarray(c["prio"], np.int32))
    hosts = make_host_table(c["n_hosts"], c["host_cores"])
    shift_ok = jnp.ones(tasks.n, bool)
    cfg = SchedulerConfig(slots_per_step=c["k"],
                          priority_levels=c["levels"])
    return tasks, hosts, shift_ok, cfg


@settings(max_examples=50, deadline=None)
@given(admission_case())
def test_presorted_schedule_matches_level_major(c):
    """Permute once + plain-FIFO select (the engine's presorted demand-scan
    path) places the same tasks on the same hosts as the level-major
    flatten, bit for bit, for arbitrary priority/arrival/footprint tables."""
    tasks, hosts, shift_ok, cfg = _admission_tables(c)
    now = jnp.float32(c["now"])
    plain, *_ = schedule_first_fit(tasks, hosts, now, shift_ok, cfg)
    order = priority_schedule_order(tasks, cfg.priority_levels)
    pre, *_ = schedule_first_fit(permute_task_table(tasks, order), hosts,
                                   now, shift_ok[order], cfg, presorted=True)
    pre = permute_task_table(pre, inverse_permutation(order))
    for name in ("status", "host", "first_start", "remaining"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, name)),
                                      np.asarray(getattr(pre, name)), name)


@settings(max_examples=50, deadline=None)
@given(admission_case())
def test_admission_is_exactly_once_and_level_ordered(c):
    """With unconstrained capacity the admitted set is EXACTLY the first-k
    prefix of the (priority desc, arrival) order — each eligible row at
    most once, higher classes never displaced by lower ones."""
    tasks, _, shift_ok, cfg = _admission_tables(c)
    hosts = make_host_table(1, 10_000)  # capacity never binds
    now = jnp.float32(c["now"])
    out, *_ = schedule_first_fit(tasks, hosts, now, shift_ok, cfg)
    placed = np.asarray(out.status) == RUNNING
    elig = np.asarray(tasks.arrival) <= c["now"]
    idx = np.nonzero(elig)[0]
    prio = np.asarray(tasks.priority)
    expect = np.zeros_like(placed)
    expect[idx[np.lexsort((idx, -prio[idx]))][:c["k"]]] = True
    np.testing.assert_array_equal(placed, expect)
    # exactly-once: every placed row landed on a real host, once
    assert np.all(np.asarray(out.host)[placed] == 0)
    assert np.all(np.asarray(out.first_start)[placed] == c["now"])
    assert np.all(~np.isfinite(np.asarray(out.first_start)[~placed]))
