"""Pallas kernel correctness: every kernel sweeps shapes/dtypes against the
pure-jnp oracle in kernels/ref.py (interpret mode on CPU); the placement
kernel's oracle is the scheduler's own `while_loop`."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.core.config import PowerModelConfig
from repro.kernels import ops, ref
from repro.kernels.ssd_chunk import ssd_intra_chunk


@pytest.mark.parametrize("h", [7, 128, 1000, 2048])
@pytest.mark.parametrize("curves", [("sqrt", "linear"), ("square", "cubic")])
def test_power_carbon_kernel(h, curves):
    rng = np.random.default_rng(h)
    cpu_u = rng.uniform(0, 1, h).astype(np.float32)
    gpu_u = rng.uniform(0, 1, h).astype(np.float32)
    ngpu = rng.integers(0, 4, h).astype(np.float32)
    on = (rng.uniform(size=h) < 0.8).astype(np.float32)
    kw = dict(cpu_idle=80.0, cpu_max=250.0, cpu_curve=curves[0],
              gpu_idle=40.0, gpu_max=300.0, gpu_curve=curves[1])
    p, dc, carbon = ops.fused_power_carbon(
        cpu_u, gpu_u, ngpu, on, 350.0, 0.25,
        PowerModelConfig(80.0, 250.0, curves[0]),
        PowerModelConfig(40.0, 300.0, curves[1]))
    p_r, dc_r, carbon_r = ref.fused_power_carbon(
        cpu_u, gpu_u, ngpu, on, 350.0, 0.25, **kw)
    np.testing.assert_allclose(p, p_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dc, dc_r, rtol=1e-4)
    np.testing.assert_allclose(carbon, carbon_r, rtol=1e-4)


@pytest.mark.parametrize("h", [7, 128, 1000, 2048])
@pytest.mark.parametrize("wb,sp", [(30.0, 24.0), (10.0, 24.0), (21.0, 24.0),
                                   (25.0, 18.0)])
def test_facility_power_kernel(h, wb, sp):
    """Fused power+cooling kernel == host_power_kw + core/thermal.py."""
    from repro.core.config import CoolingConfig
    from repro.core.power import host_power_kw
    from repro.core.thermal import cooling_step
    rng = np.random.default_rng(h + int(wb))
    cpu_u = rng.uniform(0, 1, h).astype(np.float32)
    gpu_u = rng.uniform(0, 1, h).astype(np.float32)
    ngpu = rng.integers(0, 4, h).astype(np.float32)
    on = (rng.uniform(size=h) < 0.8).astype(np.float32)
    cpu_cfg = PowerModelConfig(80.0, 250.0, "sqrt")
    gpu_cfg = PowerModelConfig(40.0, 300.0, "linear")
    ccfg = CoolingConfig(enabled=True)
    p, it, cool, water = ops.facility_power(cpu_u, gpu_u, ngpu, on, wb, sp,
                                            cpu_cfg, gpu_cfg, ccfg)
    p_ref = host_power_kw(cpu_u, gpu_u, ngpu, on, cpu_cfg, gpu_cfg)
    it_ref = jnp.sum(p_ref)
    cool_ref, water_ref = cooling_step(it_ref, wb, ccfg, setpoint_c=sp)
    np.testing.assert_allclose(p, p_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(it, it_ref, rtol=1e-4)
    np.testing.assert_allclose(cool, cool_ref, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(water, water_ref, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("r,h", [(1, 7), (4, 128), (3, 1000)])
def test_facility_power_batched_matches_per_region_loop(r, h):
    """The fleet-batched path (vmap over the pallas_call's batching rule)
    == R independent kernel launches, per region and per output."""
    from repro.core.config import CoolingConfig
    rng = np.random.default_rng(r * h)
    cpu_u = rng.uniform(0, 1, (r, h)).astype(np.float32)
    gpu_u = rng.uniform(0, 1, (r, h)).astype(np.float32)
    ngpu = rng.integers(0, 4, (r, h)).astype(np.float32)
    on = (rng.uniform(size=(r, h)) < 0.8).astype(np.float32)
    wb = rng.uniform(5.0, 35.0, r).astype(np.float32)
    sp = rng.uniform(18.0, 28.0, r).astype(np.float32)
    cpu_cfg = PowerModelConfig(80.0, 250.0, "sqrt")
    gpu_cfg = PowerModelConfig(40.0, 300.0, "linear")
    ccfg = CoolingConfig(enabled=True)
    p, it, cool, water = ops.facility_power_batched(
        cpu_u, gpu_u, ngpu, on, wb, sp, cpu_cfg, gpu_cfg, ccfg)
    assert p.shape == (r, h) and it.shape == (r,)
    for i in range(r):
        p_i, it_i, cool_i, water_i = ops.facility_power(
            cpu_u[i], gpu_u[i], ngpu[i], on[i], wb[i], sp[i],
            cpu_cfg, gpu_cfg, ccfg)
        np.testing.assert_allclose(p[i], p_i, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(it[i], it_i, rtol=1e-4)
        np.testing.assert_allclose(cool[i], cool_i, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(water[i], water_i, rtol=1e-4, atol=1e-6)


def _place_both(need_c, need_g, n_cand, free_c, free_g, usable=None,
                slots=None, host_order=None):
    """(kernel, loop) outputs of one placement: the Pallas kernel through
    `ops.first_fit` (interpret mode on the CPU) and its oracle, the
    scheduler's `while_loop`, on the inputs `schedule_first_fit` builds:
    the first `n_cand` slots valid, the rest -1 candidates with zero needs,
    the suffix minima over the valid ones and the valid count clipped by a
    traced `slots`."""
    k, h = len(need_c), len(free_c)
    valid = np.arange(k) < n_cand
    need_c = np.where(valid, need_c, 0.0).astype(np.float32)
    need_g = np.where(valid, need_g, 0.0).astype(np.float32)
    suf = [np.minimum.accumulate(np.where(valid, x, np.inf)[::-1])[::-1]
           .astype(np.float32) for x in (need_c, need_g)]
    n_valid = jnp.int32(min(n_cand, k if slots is None else slots))
    usable = np.ones(h, bool) if usable is None else usable
    free_c = np.asarray(free_c, np.float32) * usable
    free_g = np.asarray(free_g, np.float32) * usable
    args = [jnp.asarray(x) for x in (need_c, need_g, *suf)]
    args += [n_valid] + [jnp.asarray(x) for x in (free_c, free_g, usable)]
    order = None if host_order is None else jnp.asarray(host_order, jnp.int32)
    return (ops.first_fit(*args, order),
            scheduler.first_fit_loop(*args, order))


def _assert_same_placement(got, want):
    (sel, iters), (sel_w, iters_w) = got, want
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_w))
    assert int(iters) == int(iters_w)


@pytest.mark.parametrize("k,h", [(4, 3), (16, 64), (64, 300)])
def test_first_fit_kernel(k, h):
    rng = np.random.default_rng(k * h)
    cand_c = rng.integers(1, 8, k).astype(np.float32)
    cand_g = rng.integers(0, 2, k).astype(np.float32)
    free_c = rng.integers(0, 16, h).astype(np.float32)
    free_g = rng.integers(0, 4, h).astype(np.float32)
    got, want = _place_both(cand_c, cand_g, k, free_c, free_g)
    _assert_same_placement(got, want)
    assert (np.asarray(got[0]) >= 0).any()


def _gpu_bound(rng, k, h):
    """GPU hosts behind a backlog of 1-4 GPU tasks: many candidates fail."""
    return dict(need_c=rng.integers(1, 8, k), need_g=rng.integers(1, 5, k),
                n_cand=k, free_c=rng.integers(8, 48, h),
                free_g=rng.integers(0, 3, h))


def _down_hosts(rng, k, h):
    """Down and deactivated hosts (free capacity 0) beside zero-footprint
    tasks, which `0 >= 0` would otherwise park on them."""
    need_c = rng.integers(0, 3, k) * (rng.uniform(size=k) < 0.5)
    usable = rng.uniform(size=h) < 0.4
    usable[0] = False
    return dict(need_c=need_c, need_g=np.zeros(k), n_cand=k,
                free_c=rng.integers(0, 4, h), free_g=np.zeros(h),
                usable=usable)


def _minus_one_tail(rng, k, h):
    return dict(need_c=rng.integers(1, 8, k), need_g=np.zeros(k),
                n_cand=k // 3, free_c=rng.integers(0, 16, h),
                free_g=np.zeros(h))


def _slots_below_k(rng, k, h):
    return dict(need_c=rng.integers(1, 8, k), need_g=np.zeros(k), n_cand=k,
                free_c=rng.integers(0, 16, h), free_g=np.zeros(h), slots=5)


def _host_order(rng, k, h):
    return dict(need_c=rng.integers(1, 8, k), need_g=rng.integers(0, 2, k),
                n_cand=k, free_c=rng.integers(0, 16, h),
                free_g=rng.integers(0, 4, h), usable=rng.uniform(size=h) < .9,
                host_order=rng.permutation(h))


def _saturated(rng, k, h):
    """Nearly full hosts behind a backlog whose later half fits nowhere:
    the suffix-minimum early stop ends the loop."""
    room = rng.uniform(size=h) < 0.05
    need_c = np.where(np.arange(k) < k // 2, rng.integers(4, 8, k),
                      rng.integers(12, 16, k))
    return dict(need_c=need_c, need_g=np.zeros(k), n_cand=k,
                free_c=np.where(room, rng.integers(4, 8, h),
                                rng.integers(0, 4, h)),
                free_g=np.zeros(h))


CASES = pytest.mark.parametrize(
    "case", [_gpu_bound, _down_hosts, _minus_one_tail, _slots_below_k,
             _host_order, _saturated], ids=lambda f: f.__name__.strip("_"))


@pytest.mark.parametrize("h", [277, 972])
@CASES
def test_first_fit_kernel_matches_the_loop(case, h):
    """The kernel places every slot and counts every iteration as the
    scheduler's `while_loop` does, element for element."""
    _check_case(case, 64, h)


@CASES
def test_first_fit_kernel_matches_the_loop_at_borg_width(case):
    """The same at Borg's 4,096 slots a step over its 1,534 hosts: each
    slot writes its own row of the result, 32 rows of 128 slots."""
    _check_case(case, 4096, 1534)


def _check_case(case, k, h):
    rng = np.random.default_rng(h)
    inputs = case(rng, k, h)
    got, want = _place_both(**inputs)
    _assert_same_placement(got, want)
    sel, iters = np.asarray(got[0]), int(got[1])
    n_valid = min(inputs["n_cand"], inputs.get("slots") or k)
    assert (sel[n_valid:] == -1).all() and iters <= n_valid
    if case is _gpu_bound:
        assert (sel[:iters] == -1).any() and (sel[:iters] >= 0).any()
    if case is _down_hosts:
        placed = sel[sel >= 0]
        assert placed.size and inputs["usable"][placed].all()
    if case is _host_order:
        assert (sel >= 0).any()
    if case is _saturated:
        assert iters < n_valid


@pytest.mark.parametrize("shape", [(1, 2, 16, 4, 8, 1, 8),
                                   (2, 4, 32, 8, 16, 2, 16),
                                   (1, 1, 64, 16, 32, 4, 32)])
def test_ssd_intra_chunk_kernel(shape):
    """Pallas intra-chunk vs the jnp segsum path inside ssd_scan."""
    bt, nc, q, h, p, g, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    xdt = rng.standard_normal((bt, nc, q, h, p)).astype(np.float32) * 0.3
    da = -np.abs(rng.standard_normal((bt, nc, h, q)).astype(np.float32)) * 0.2
    bmat = rng.standard_normal((bt, nc, q, h, n)).astype(np.float32) * 0.3
    cmat = rng.standard_normal((bt, nc, q, h, n)).astype(np.float32) * 0.3

    y_pallas = ssd_intra_chunk(xdt, da, bmat, cmat, interpret=True)

    # jnp oracle (same math as models/ssm.ssd_scan intra path)
    from repro.models.ssm import _segsum
    decay = jnp.exp(_segsum(jnp.asarray(da)))
    cb = jnp.einsum("bcqhs,bckhs->bchqk", cmat, bmat)
    y_ref = jnp.einsum("bchqk,bckhp->bcqhp", cb * decay, xdt)
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_kernel_end_to_end_matches_sequential_oracle():
    """Full chunked scan with the Pallas intra kernel == exact recurrence."""
    from repro.models.ssm import ssd_scan
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    B, S, H, Pd, G, N = 2, 64, 4, 8, 2, 16
    x = jax.random.normal(ks[0], (B, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    b = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_k, _ = ssd_scan(x, dt, a, b, c, chunk=16, use_pallas=True)
    y_ref = jax.vmap(lambda xx, dd, bb, cc: ref.ssd_chunk(xx, dd, a, bb, cc))(
        x, dt, b, c)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_mamba_block_pallas_path():
    """mamba2 block with use_pallas=True == jnp path."""
    from repro.configs import reduced
    from repro.models import ssm
    cfg = reduced("mamba2-2.7b")
    model_defs = ssm.ssm_block_defs(cfg)
    from repro.models import layers as L
    params = L.init_params(model_defs, jax.random.PRNGKey(0), "float32")
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5
    y_jnp = ssm.mamba2_block(cfg, params, u, use_pallas=False)
    y_pal = ssm.mamba2_block(cfg, params, u, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_jnp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [
    # (b, sq, sk, h, kv, d, causal, bq, bk)
    (2, 64, 64, 4, 2, 16, True, 16, 16),
    (1, 128, 128, 8, 8, 32, True, 32, 64),
    (2, 32, 96, 4, 1, 16, False, 16, 32),   # MQA cross-attention shape
    (1, 48, 48, 2, 2, 8, True, 48, 16),
])
def test_flash_attention_kernel(shape):
    from repro.kernels.flash_attn import flash_attention
    from repro.models import layers as L
    b, sq, sk, h, kv, d, causal, bq, bk = shape
    ks = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, kv, d))
    v = jax.random.normal(ks[2], (b, sk, kv, d))
    mask = L.causal_mask(sq, sk) if causal else jnp.ones((sq, sk), bool)
    ref = L.sdpa(q, k, v, mask, 0.35)
    got = flash_attention(q, k, v, scale=0.35, causal=causal,
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attn import flash_attention
    from repro.models import layers as L
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.bfloat16)
    ref = L.sdpa(q, k, v, L.causal_mask(64, 64), 0.25)
    got = flash_attention(q, k, v, scale=0.25, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
