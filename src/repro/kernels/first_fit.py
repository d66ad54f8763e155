"""First-fit placement as one Pallas kernel: the TPU form of the placement
loop in `core/scheduler.schedule_first_fit`.

The loop is sequential over the step's candidate slots (each placement
changes the free capacity the next slot reads) and vector-wide over hosts.
As an XLA `while_loop` every iteration is a handful of separate device ops
plus the loop's own handshake, ~12 us on a TPU v5e for well under a
microsecond of vector work.  Here every slot of the step runs inside one
launch, and the free-core and free-GPU vectors stay in VMEM throughout.

It computes exactly what the scheduler's `first_fit_loop` computes, with
that loop's own arithmetic, so every placement and the iteration count are
bitwise the loop's:

  * slot i runs only while i < n_valid, the running flag has stayed up, and
    some usable host clears the suffix minima (suf_c[i], suf_g[i]) of the
    remaining slots' needs — the loop's early stop; the count is the number
    of slots in which that held;
  * the first fitting host in preference order is the fitting host of
    least rank (natural order: rank = host index; a `host_order`
    permutation: its inverse);
  * `free - where(hit, need, 0.0)` is the loop's `free - where(hidx == hj,
    take, 0.0)` on every host.

Mosaic has no scalar access to VMEM and no dynamic lane reads: the slots'
needs and the trip count sit in SMEM, read by the loop index (`f32[4, k]`,
64 KiB of the v5e's 1 MiB at 4,096 slots), and slot i's host is written
into its own result row, `i // 128`, through a lane mask, so a slot costs
the same at any k.  Host vectors are padded to `[ceil(H/128), 128]` with
hosts that are never usable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
# rows of the f32[4, k] SMEM needs block
_NEED_C, _NEED_G, _SUF_C, _SUF_G = range(4)


def _kernel(n_ref, need_ref, free_c_ref, free_g_ref, usable_ref, *rest,
            ordered: bool):
    if ordered:
        rank_ref, sel_ref, iters_ref = rest
    else:
        sel_ref, iters_ref = rest
    rows = free_c_ref.shape[0]
    big = rows * _LANE                  # above every real host's rank
    hidx = (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 0) * _LANE
            + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1))
    rank = rank_ref[...] if ordered else hidx
    usable = usable_ref[...] != 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    def vmin(x):                        # (1, 1): stays a vector
        return jnp.min(x, axis=(0, 1), keepdims=True)

    def body(i, carry):
        free_c, free_g, run, iters = carry
        clear = ((free_c >= need_ref[_SUF_C, i])
                 & (free_g >= need_ref[_SUF_G, i]) & usable)
        run = jnp.minimum(run, jnp.max(clear.astype(jnp.int32), axis=(0, 1),
                                       keepdims=True))
        need_c = need_ref[_NEED_C, i]
        need_g = need_ref[_NEED_G, i]
        fits = (free_c >= need_c) & (free_g >= need_g) & usable
        first = vmin(jnp.where(fits, rank, big))
        placed = (first < big) & (run > 0)
        hit = (rank == first) & placed
        free_c = free_c - jnp.where(hit, need_c, 0.0)
        free_g = free_g - jnp.where(hit, need_g, 0.0)
        host = vmin(jnp.where(hit, hidx, big)) if ordered else first
        row = sel_ref.at[pl.ds(i // _LANE, 1), :]
        row[...] = jnp.where(lane == i % _LANE,
                             jnp.where(placed, host, -1), row[...])
        return free_c, free_g, run, iters + run

    sel_ref[...] = jnp.full(sel_ref.shape, -1, jnp.int32)
    one = jnp.ones((1, 1), jnp.int32)
    _, _, _, iters = jax.lax.fori_loop(
        0, n_ref[0], body, (free_c_ref[...], free_g_ref[...], one, 0 * one))
    iters_ref[...] = jnp.broadcast_to(iters, (1, _LANE))


@functools.partial(jax.jit, static_argnames=("interpret",))
def first_fit(need_c, need_g, suf_c, suf_g, n_valid, free_c, free_g, usable,
              rank=None, *, interpret: bool = False):
    """First-fit of k slots onto H hosts in one kernel launch.

    need_c/need_g: f32[k] slot needs; suf_c/suf_g: f32[k] their suffix
    minima over the valid slots (+inf past them); n_valid: i32 count of
    valid slots (a prefix); free_c/free_g: f32[H]; usable: bool[H];
    rank: i32[H] preference rank of each host (None: host index).
    Returns (sel_host i32[k], -1 where nothing was placed, iters i32).
    """
    k = need_c.shape[0]
    h = free_c.shape[0]
    rows = -(-h // _LANE)
    k_rows = -(-k // _LANE)

    def hostvec(x, dtype, fill):
        return jnp.pad(jnp.asarray(x, dtype), (0, rows * _LANE - h),
                       constant_values=fill).reshape(rows, _LANE)

    needs = jnp.stack([need_c, need_g, suf_c, suf_g]).astype(jnp.float32)
    host_in = [hostvec(free_c, jnp.float32, 0.0),
               hostvec(free_g, jnp.float32, 0.0),
               hostvec(usable, jnp.int32, 0)]
    if rank is not None:
        host_in.append(hostvec(rank, jnp.int32, rows * _LANE))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    sel, iters = pl.pallas_call(
        functools.partial(_kernel, ordered=rank is not None),
        in_specs=[smem, smem] + [vmem] * len(host_in),
        out_specs=[vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((k_rows, _LANE), jnp.int32),
                   jax.ShapeDtypeStruct((1, _LANE), jnp.int32)],
        interpret=interpret,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), needs, *host_in)
    return sel.reshape(-1)[:k], iters[0, 0]
