"""The scheduler's placement-loop counters (`first_fit_iters`,
`first_fit_placed`).

`schedule_first_fit` returns the while_loop's iterations and the slots it
placed beside the task table; the scheduler stage sums both into
`MetricsAcc`, and `summarize` carries them into `SimResult`.  Pinned here
against a plain NumPy re-count of the loop's rule, and across the two step
backends, which run the same scheduler stage; and the placement kernel
(the TPU's form, in interpret mode here) against the `while_loop` over a
whole simulation.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import jax

from repro.core import (FailureConfig, ResilienceConfig, SchedulerConfig,
                        SimConfig, make_host_table, make_task_table, scheduler,
                        simulate, summarize, telemetry)
from repro.core.scheduler import schedule_first_fit, schedule_step
from repro.core.state import PENDING, RUNNING


def _numpy_first_fit(tasks, hosts, now, k, slots=None):
    """(iterations, placements) of one first-fit pass, by the loop's rule:
    FIFO candidates, first host that fits, stop at the first empty slot, at
    `slots`, or once no usable host clears the remaining candidates'
    component-wise minimum needs."""
    status, host = np.asarray(tasks.status), np.asarray(tasks.host)
    cores, gpus = np.asarray(tasks.cores), np.asarray(tasks.gpus)
    usable = np.asarray(hosts.active) & np.asarray(hosts.up)
    free_c = np.asarray(hosts.cores) * usable
    free_g = np.asarray(hosts.n_gpus) * usable
    for t in np.nonzero((status == RUNNING) & (host >= 0))[0]:
        free_c[host[t]] -= cores[t]
        free_g[host[t]] -= gpus[t]
    elig = (status == PENDING) & (np.asarray(tasks.arrival) <= now)
    cand = np.nonzero(elig)[0][:k]
    need_c, need_g = cores[cand], gpus[cand]
    suf_c = np.minimum.accumulate(need_c[::-1])[::-1]
    suf_g = np.minimum.accumulate(need_g[::-1])[::-1]
    i = placed = 0
    while (i < len(cand) and (slots is None or i < slots)
           and np.any((free_c >= suf_c[i]) & (free_g >= suf_g[i]) & usable)):
        fits = (free_c >= need_c[i]) & (free_g >= need_g[i]) & usable
        if fits.any():
            h = int(np.argmax(fits))
            free_c[h] -= need_c[i]
            free_g[h] -= need_g[i]
            placed += 1
        i += 1
    return i, placed


def _table(seed, n=40, n_hosts=5):
    rng = np.random.default_rng(seed)
    tasks = make_task_table(np.sort(rng.uniform(0.0, 6.0, n)),
                            rng.uniform(0.5, 4.0, n),
                            rng.choice([1.0, 2.0, 4.0, 8.0], n),
                            gpus=rng.choice([0.0, 0.0, 1.0], n))
    # a third of the early tasks already running somewhere: fragmented hosts
    running = rng.uniform(size=n) < 0.3
    hosts = make_host_table(n_hosts, 8, gpus_per_host=1)
    hosts = hosts._replace(up=jnp.asarray(rng.uniform(size=n_hosts) < 0.85))
    tasks = tasks._replace(
        status=jnp.where(running, RUNNING, PENDING).astype(jnp.int32),
        host=jnp.where(running, jnp.asarray(rng.integers(0, n_hosts, n)),
                       -1).astype(jnp.int32))
    return tasks, hosts


@pytest.mark.parametrize("seed, k, slots", [
    (0, 16, None), (1, 16, None), (2, 4, None), (3, 16, 5), (4, 64, None),
    (5, 2, None)])
def test_counters_match_a_numpy_recount_of_the_loop(seed, k, slots):
    tasks, hosts = _table(seed)
    now = 4.0
    cfg = SchedulerConfig(slots_per_step=k)
    out, iters, placed, binds = schedule_first_fit(
        tasks, hosts, jnp.float32(now), jnp.ones(tasks.n, bool), cfg,
        slots=None if slots is None else jnp.int32(slots))
    want_iters, want_placed = _numpy_first_fit(tasks, hosts, now, k, slots)
    assert (float(iters), float(placed)) == (want_iters, want_placed)
    n_elig = int(np.sum((np.asarray(tasks.status) == PENDING)
                        & (np.asarray(tasks.arrival) <= now)))
    assert float(binds) == float(n_elig > min(k, slots or k))
    newly = (np.asarray(out.status) == RUNNING) & \
        (np.asarray(tasks.status) == PENDING)
    assert int(newly.sum()) == want_placed


def test_aggregate_mode_counts_no_placement_loop():
    tasks, hosts = _table(0)
    _, iters, placed, binds = schedule_step(
        tasks, hosts, jnp.float32(4.0), jnp.ones(tasks.n, bool),
        SchedulerConfig(mode="aggregate"))
    assert float(iters) == float(placed) == float(binds) == 0.0


def test_counters_agree_between_backends_and_reach_the_summary():
    rng = np.random.default_rng(11)
    n, steps = 60, 96
    tasks = make_task_table(np.sort(rng.uniform(0.0, 12.0, n)),
                            rng.uniform(0.5, 6.0, n),
                            rng.choice([1.0, 2.0, 4.0], n))
    hosts = make_host_table(3, 4)
    ci = (300 + 100 * np.sin(np.arange(steps) / 8.0)).astype(np.float32)
    res = {}
    for backend in ("stage-pipeline", "megakernel"):
        cfg = SimConfig(n_steps=steps, backend=backend)
        final, _ = simulate(tasks, hosts, ci, cfg)
        res[backend] = (final.metrics, summarize(final, cfg))
    (m_s, r_s), (m_m, r_m) = res["stage-pipeline"], res["megakernel"]
    assert float(m_s.first_fit_iters) == float(m_m.first_fit_iters)
    assert float(m_s.first_fit_placed) == float(m_m.first_fit_placed)
    assert float(r_s.first_fit_iters) == float(m_s.first_fit_iters)
    assert float(r_s.first_fit_placed) == float(m_s.first_fit_placed)
    # every task that started was placed exactly once by the loop, and the
    # loop ran at least once per placement
    assert float(r_s.first_fit_placed) == float(r_s.n_started)
    assert float(r_s.first_fit_iters) >= float(r_s.first_fit_placed) > 0


def _placement_run(failures: bool):
    """A few hundred CPU and GPU tasks on 24 hosts over two days, behind a
    backlog; with host failures, placement follows `resilience.host_rank`."""
    rng = np.random.default_rng(17)
    n, steps = 300, 192
    tasks = make_task_table(np.sort(rng.uniform(0.0, 40.0, n)),
                            rng.uniform(0.5, 8.0, n),
                            rng.choice([1.0, 2.0, 4.0, 8.0], n),
                            gpus=rng.choice([0.0, 0.0, 1.0, 2.0], n))
    hosts = make_host_table(24, 8, gpus_per_host=2)
    ci = (300 + 100 * np.sin(np.arange(steps) / 8.0)).astype(np.float32)
    cfg = SimConfig(n_steps=steps, backend="megakernel",
                    scheduler=SchedulerConfig(slots_per_step=16))
    if failures:
        cfg = cfg.replace(
            failures=FailureConfig(enabled=True, mtbf_h=60.0, repair_h=3.0),
            resilience=ResilienceConfig(enabled=True, reactive_placement=True))
    return tasks, hosts, ci, cfg


def _forms_run(monkeypatch, out_dir, form, fn):
    """`fn()` with the placement form forced, under a telemetry session:
    (its result, the form the session noted last)."""
    monkeypatch.setattr(scheduler, "first_fit_form", lambda: form)
    with telemetry.session(out_dir=str(out_dir), export=False) as tel:
        out = fn()
        assert tel.last_first_fit is not None
        return out, tel.last_first_fit


@pytest.mark.parametrize("failures", [False, True])
def test_placement_kernel_simulates_like_the_loop(monkeypatch, tmp_path,
                                                  failures):
    """Forced onto the placement kernel, a simulation's every total and
    both loop counters are bitwise the `while_loop`'s."""
    tasks, hosts, ci, cfg = _placement_run(failures)

    def run():
        return summarize(simulate(tasks, hosts, ci, cfg)[0], cfg)

    loop, loop_form = _forms_run(monkeypatch, tmp_path, "while_loop", run)
    kern, kern_form = _forms_run(monkeypatch, tmp_path, "pallas", run)
    assert (loop_form, kern_form) == ("while_loop", "pallas")
    assert float(loop.first_fit_iters) > float(loop.first_fit_placed) > 0
    for field in loop._fields:
        a, b = getattr(loop, field), getattr(kern, field)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                          err_msg=field)


def test_batched_placement_keeps_the_loop(monkeypatch, tmp_path):
    """A call whose placement is batched by `vmap` (here over the traced
    slot bound) takes the `while_loop` even where the kernel is the form,
    and each lane places as the unbatched run does: the same counts, and
    totals equal up to the summation order of batched float reductions
    (bitwise on the CPU; the TPU's batched carbon sum reads 1 ulp off)."""
    tasks, hosts, ci, cfg = _placement_run(False)
    k = cfg.scheduler.slots_per_step

    def run(slots):
        final, _ = simulate(tasks, hosts, ci, cfg,
                            dyn={"slots_per_step": slots})
        return summarize(final, cfg)

    single, _ = _forms_run(monkeypatch, tmp_path, "pallas",
                           lambda: run(jnp.int32(k)))
    lanes, form = _forms_run(
        monkeypatch, tmp_path, "pallas",
        lambda: jax.vmap(run)(jnp.asarray([k, k], jnp.int32)))
    assert form == "while_loop"
    counts = ("first_fit_iters", "first_fit_placed", "slot_bound_steps",
              "n_started", "n_done",
              "n_decided", "n_tasks")
    for field in single._fields:
        a, b = getattr(single, field), getattr(lanes, field)
        if a is None:
            continue
        for lane in range(2):
            if field in counts:
                np.testing.assert_array_equal(
                    np.asarray(b)[lane], np.asarray(a), err_msg=field)
            else:
                np.testing.assert_allclose(
                    np.asarray(b)[lane], np.asarray(a), rtol=1e-6,
                    err_msg=field)
