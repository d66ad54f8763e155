"""The scheduler's placement-loop counters (`first_fit_iters`,
`first_fit_placed`).

`schedule_first_fit` returns the while_loop's iterations and the slots it
placed beside the task table; the scheduler stage sums both into
`MetricsAcc`, and `summarize` carries them into `SimResult`.  Pinned here
against a plain NumPy re-count of the loop's rule, and across the two step
backends, which run the same scheduler stage.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SchedulerConfig, SimConfig, make_host_table,
                        make_task_table, simulate, summarize)
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
    out, iters, placed = schedule_first_fit(
        tasks, hosts, jnp.float32(now), jnp.ones(tasks.n, bool), cfg,
        slots=None if slots is None else jnp.int32(slots))
    want_iters, want_placed = _numpy_first_fit(tasks, hosts, now, k, slots)
    assert (float(iters), float(placed)) == (want_iters, want_placed)
    newly = (np.asarray(out.status) == RUNNING) & \
        (np.asarray(tasks.status) == PENDING)
    assert int(newly.sum()) == want_placed


def test_aggregate_mode_counts_no_placement_loop():
    tasks, hosts = _table(0)
    _, iters, placed = schedule_step(tasks, hosts, jnp.float32(4.0),
                                     jnp.ones(tasks.n, bool),
                                     SchedulerConfig(mode="aggregate"))
    assert float(iters) == 0.0 and float(placed) == 0.0


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
