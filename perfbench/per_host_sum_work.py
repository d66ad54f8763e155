"""The work of the per-host sums, from the deployment's shapes.

Every simulated step runs two per-host sums over the task table
(`free_capacity` and `host_utilization` in core/scheduler.py):

* free capacity reads `status`, `host`, `cores` and `gpus`, and sums the
  running tasks' cores and GPUs per host;
* utilization reads the same and `cpu_util` and `gpu_util`, and sums
  cores x CPU utilization and GPUs x GPU utilization per host;
* each writes one `[H, 2]` f32 result.

Every column is 4 bytes.  The count depends on the task count T, the host
count H and the steps only, never on the form that computes the sums (a
one-hot contraction on the TPU, `segment_sum` elsewhere): the bytes are
what the sums must read and write once, the operations the adds and
multiplies of the sums themselves.
"""
from __future__ import annotations

_COLUMN_BYTES = 4
_FREE_CAPACITY_COLUMNS = 4     # status, host, cores, gpus
_UTILIZATION_COLUMNS = 6       # the same, cpu_util, gpu_util
_RESULT_BYTES = 2 * 2 * 4      # two [H, 2] f32 results, per host
_OPS_PER_TASK = 6              # 2 adds; 2 multiplies and 2 adds


def work(n_tasks: int, n_hosts: int, n_steps: int) -> tuple[float, float]:
    """(operations, bytes) of the two per-host sums over `n_steps` steps of
    one scenario."""
    per_step = (n_tasks * _COLUMN_BYTES
                * (_FREE_CAPACITY_COLUMNS + _UTILIZATION_COLUMNS)
                + n_hosts * _RESULT_BYTES)
    return float(n_tasks * _OPS_PER_TASK * n_steps), float(per_step * n_steps)


def command_line_sizes():
    """(T, H) of the deployment that the cell and `--seed` of
    perfbench/run.py's command line make; None where it names no cell."""
    from perfbench import fresh_scopes, generator, manifest
    named = fresh_scopes.command_line()
    if named is None:
        return None
    workload, seed = named
    dep = generator.deployment(manifest.cell(workload).config, seed)
    return int(dep.arrival.shape[0]), dep.n_hosts
