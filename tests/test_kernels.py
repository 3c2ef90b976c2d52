"""Per-kernel allclose sweeps: every Pallas kernel vs its ref.py oracle,
across shapes and dtypes (interpret=True on this CPU host)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ----------------------------------------------------------------------
# flash_attention
# ----------------------------------------------------------------------
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,Kv,D,causal,window",
    [
        (2, 256, 256, 4, 2, 64, True, None),      # GQA causal
        (1, 300, 300, 4, 4, 64, True, None),      # MHA, ragged (pad path)
        (2, 128, 512, 8, 2, 128, True, None),     # q suffix of k (q_offset)
        (1, 256, 256, 2, 1, 64, True, 128),       # MQA + sliding window
        (1, 200, 200, 4, 2, 64, False, None),     # non-causal (encoder)
        (1, 512, 512, 2, 2, 128, True, 64),       # small window, banded skip
    ])
def test_flash_attention_matches_ref(B, Sq, Sk, H, Kv, D, causal, window,
                                     dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Kv, D), dtype)
    qo = Sk - Sq
    out = flash_attention(q, k, v, causal=causal, window=window, q_offset=qo,
                          block_q=128, block_k=128)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=qo)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_matches_production_path():
    """Kernel vs the chunked XLA attention the models actually run."""
    from repro.models.attention import attention_prefill
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=96, block_q=128,
                          block_k=128)
    prod = attention_prefill(q, k, v, causal=True, window=96,
                             q_chunk=128, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(prod),
                               atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------------
# crossbar_dispatch
# ----------------------------------------------------------------------
from repro.kernels.crossbar_dispatch.ops import (crossbar_combine,
                                                 crossbar_dispatch,
                                                 crossbar_plan)
from repro.kernels.crossbar_dispatch import ref as xref


@pytest.mark.parametrize("T,S,C,D,block_t", [
    (512, 4, 64, 128, 128),
    (300, 8, 32, 64, 128),      # pad path
    (1024, 16, 128, 256, 256),
    (64, 4, 8, 128, 64),        # capacity overflow drops
])
def test_crossbar_kernels_match_ref(T, S, C, D, block_t):
    ks = jax.random.split(jax.random.key(2), 4)
    dst = jax.random.randint(ks[0], (T,), 0, S)
    x = jax.random.normal(ks[1], (T, D), jnp.float32)
    w = jax.random.uniform(ks[2], (T,), jnp.float32)
    allowed = (jax.random.uniform(ks[3], (S,)) > 0.25).astype(jnp.int32)
    quota = jnp.where(jnp.arange(S) % 3 == 0, 0, C // 2).astype(jnp.int32)
    cap = jnp.full((S,), C, jnp.int32)

    keep, slot, err, counts = crossbar_plan(dst, allowed, quota, cap,
                                            block_t=block_t)
    kr, sr, er, cr = xref.plan_ref(dst, allowed, quota, cap, S)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cr))

    slab = crossbar_dispatch(x, dst, keep, slot, n_ports=S, capacity=C,
                             block_t=block_t)
    np.testing.assert_allclose(
        np.asarray(slab), np.asarray(xref.scatter_ref(x, dst, keep, slot,
                                                      S, C)), atol=1e-6)

    y = slab * 1.5
    back = crossbar_combine(y, dst, keep, slot, w, block_t=block_t)
    np.testing.assert_allclose(
        np.asarray(back), np.asarray(xref.combine_ref(y, dst, keep, slot,
                                                      w)), atol=1e-5)


@pytest.mark.parametrize("T,n,D,block_t", [
    (1024, 8, 1024, 256),       # 4 token blocks, 2 D tiles
    (640, 5, 256, 128),         # 5 token blocks, odd port count
])
def test_crossbar_kernels_match_ref_across_blocks_with_binding_quotas(
        T, n, D, block_t):
    """The fused plan and the kernel data plane over several token blocks
    (nb > 1), with WRR quotas that bind mid-stream: the per-pair live
    counts carried between blocks must reproduce ``ref.plan_multi_ref``
    bit for bit, and the backend's slabs and combine must equal the
    ``core/arbiter`` scatter/gather of the oracle plan."""
    from repro.core import arbiter
    from repro.core.registers import CrossbarRegisters, ErrorCode
    from repro.fabric import Fabric
    from repro.kernels.crossbar_dispatch.kernel import plan_multi_call

    rng = np.random.default_rng(T + n)
    dst = jnp.asarray(rng.integers(-1, n, T), jnp.int32)
    src = jnp.asarray(rng.integers(0, n, T), jnp.int32)
    allowed = jnp.asarray(rng.random((n, n)) > 0.2)
    quota = jnp.asarray(rng.integers(1, T // (n * n), (n, n)), jnp.int32)
    assert T // block_t > 1

    got = plan_multi_call(dst, src, allowed.astype(jnp.int32), quota.T,
                          n_ports=n, block_t=block_t, interpret=True)
    want = xref.plan_multi_ref(dst, src, allowed, quota.T, block_t)
    for name, g, w in zip(("keep", "rank", "err", "granted"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    err, rank = np.asarray(got[2]), np.asarray(got[1])
    assert (err == ErrorCode.GRANT_TIMEOUT).sum() > 0      # quotas bind
    # Ranks past the first block exceed any count one block can hold, so
    # the carried per-pair counters shaped them.
    d = np.asarray(dst)
    pair = np.where(d >= 0, np.asarray(src) * n + d, -1).reshape(-1, block_t)
    in_block = max(np.bincount(p[p >= 0]).max() for p in pair)
    assert rank[block_t:].max() >= in_block

    C = 8 * (T // (8 * n))
    regs = CrossbarRegisters.create(n, capacity=C).write(allowed=allowed,
                                                         quota=quota)
    fab = Fabric(regs, backend="pallas", data_plane="kernel",
                 block_t=block_t, kernel_mode="pallas_interpret",
                 capacity=C)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w = jnp.asarray(rng.random(T), jnp.float32)
    slabs, plan = fab.dispatch(x, dst, src)
    oracle = arbiter.wrr_dispatch_plan(dst, src, regs)
    for name in ("keep", "slot", "error", "counts", "drops"):
        np.testing.assert_array_equal(np.asarray(getattr(plan, name)),
                                      np.asarray(getattr(oracle, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(slabs), np.asarray(arbiter.dispatch(x, oracle, n, C)))
    np.testing.assert_array_equal(
        np.asarray(fab.combine(slabs, plan, weights=w)),
        np.asarray(arbiter.combine(slabs, oracle, w)))


def test_crossbar_plan_matches_core_pairwise_plan():
    """Kernel semantics == the shard_map production path's plan."""
    from repro.core.crossbar import pairwise_dispatch_plan
    from repro.core.registers import CrossbarRegisters
    S, T = 8, 256
    rng = np.random.default_rng(3)
    dst = jnp.asarray(rng.integers(0, S, T), jnp.int32)
    regs = CrossbarRegisters.create(S, capacity=16)
    regs = regs.write(quota=jnp.asarray(rng.integers(0, 8, (S, S)),
                                        jnp.int32))
    src = 3
    keep_c, slot_c, err_c = pairwise_dispatch_plan(dst, jnp.int32(src), regs,
                                                   capacity=16)
    keep_k, slot_k, err_k, _ = crossbar_plan(
        dst, regs.allowed[src].astype(jnp.int32),
        regs.quota[:, src],
        jnp.minimum(regs.capacity, 16))
    np.testing.assert_array_equal(np.asarray(keep_c).astype(np.int32),
                                  np.asarray(keep_k))
    kept = np.asarray(keep_c)
    np.testing.assert_array_equal(np.asarray(slot_c)[kept],
                                  np.asarray(slot_k)[kept])


def test_crossbar_dispatch_roundtrip_identity():
    """scatter -> combine with unit weights is the keep-masked identity."""
    T, S, C, D = 256, 4, 128, 64
    ks = jax.random.split(jax.random.key(4), 2)
    dst = jax.random.randint(ks[0], (T,), 0, S)
    x = jax.random.normal(ks[1], (T, D), jnp.float32)
    allowed = jnp.ones((S,), jnp.int32)
    quota = jnp.zeros((S,), jnp.int32)
    cap = jnp.full((S,), C, jnp.int32)
    keep, slot, _, _ = crossbar_plan(dst, allowed, quota, cap)
    slab = crossbar_dispatch(x, dst, keep, slot, n_ports=S, capacity=C)
    back = crossbar_combine(slab, dst, keep, slot, jnp.ones((T,)))
    np.testing.assert_allclose(np.asarray(back),
                               np.asarray(x * (keep > 0)[:, None]),
                               atol=1e-6)


# ----------------------------------------------------------------------
# ssd
# ----------------------------------------------------------------------
from repro.kernels.ssd.ops import ssd_scan
from repro.kernels.ssd.ref import ssd_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 512, 4, 64, 128, 256),
    (1, 256, 8, 64, 64, 128),
    (2, 384, 2, 32, 128, 128),
])
def test_ssd_kernel_matches_ref(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, S, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, N)) * 0.3).astype(dtype)
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    dA = jnp.moveaxis(dt, 2, 1) * A[None, :, None]
    yr, hr = ssd_ref(jnp.moveaxis(x, 2, 1), dA, jnp.moveaxis(dt, 2, 1),
                     Bm, Cm)
    yr = jnp.moveaxis(yr, 1, 2)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=5e-4,
                               rtol=5e-3)


def test_ssd_kernel_matches_production_chunked_path():
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.key(6), 5)
    B, S, H, P, N = 2, 512, 4, 64, 128
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=256)
    ym, hm = ssd_chunked(x, dt, A, Bm, Cm, 256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ym), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hm), atol=2e-4,
                               rtol=1e-3)


# ----------------------------------------------------------------------
# rglru
# ----------------------------------------------------------------------
from repro.kernels.rglru.ops import rglru_scan_kernel
from repro.kernels.rglru.ref import rglru_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,L,chunk,block_l", [
    (2, 512, 512, 256, 256),
    (1, 256, 1024, 128, 512),
    (3, 384, 256, 128, 256),
])
def test_rglru_kernel_matches_ref(B, S, L, chunk, block_l, dtype):
    ks = jax.random.split(jax.random.key(7), 3)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, L))) * 0.98
         + 0.01).astype(jnp.float32)
    u = (jax.random.normal(ks[1], (B, S, L)) * 0.5).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, L)) * 0.3
    h, hl = rglru_scan_kernel(u, a, h0, chunk=chunk, block_l=block_l)
    hr, hlr = rglru_ref(a, u.astype(jnp.float32), h0)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), atol=tol,
                               rtol=tol)


def test_rglru_kernel_matches_production_scan():
    from repro.models.rglru import rglru_scan
    ks = jax.random.split(jax.random.key(8), 3)
    B, S, L = 2, 256, 256
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, L))) * 0.98 + 0.01
    u = jax.random.normal(ks[1], (B, S, L)) * 0.5
    h0 = jax.random.normal(ks[2], (B, L)) * 0.3
    h, hl = rglru_scan_kernel(u, a, h0, chunk=128, block_l=128)
    hm, hlm = rglru_scan(u, a, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hm), atol=5e-5,
                               rtol=5e-4)


# ----------------------------------------------------------------------
# hamming
# ----------------------------------------------------------------------
from repro.kernels.hamming.ops import (hamming_decode, hamming_encode,
                                       multiply_const)
from repro.kernels.hamming import ref as href


@pytest.mark.parametrize("n", [100, 4096, 10000])
def test_hamming_encode_matches_ref(n):
    rng = np.random.default_rng(n)
    data = rng.integers(0, 1 << 26, size=n, dtype=np.uint32)
    np.testing.assert_array_equal(
        np.asarray(hamming_encode(jnp.asarray(data))), href.encode_ref(data))


@pytest.mark.parametrize("n", [100, 4096])
def test_hamming_decode_corrects_single_bit_errors(n):
    rng = np.random.default_rng(n + 1)
    data = rng.integers(0, 1 << 26, size=n, dtype=np.uint32)
    code = href.encode_ref(data)
    errpos = rng.integers(0, 31, size=n).astype(np.uint32)
    flip = np.where(rng.random(n) < 0.5, np.uint32(1) << errpos,
                    np.uint32(0))
    corrupted = code ^ flip
    dec, corr = hamming_decode(jnp.asarray(corrupted))
    dec_r, corr_r = href.decode_ref(corrupted)
    np.testing.assert_array_equal(np.asarray(dec), dec_r)
    np.testing.assert_array_equal(np.asarray(corr), corr_r)
    np.testing.assert_array_equal(np.asarray(dec), data)   # corrected!
    np.testing.assert_array_equal(np.asarray(corr), (flip != 0))


@pytest.mark.parametrize("constant", [3, 7, 2654435761])
def test_multiplier_matches_ref(constant):
    rng = np.random.default_rng(constant)
    data = rng.integers(0, 1 << 32, size=3000, dtype=np.uint32)
    np.testing.assert_array_equal(
        np.asarray(multiply_const(jnp.asarray(data), constant)),
        href.multiply_ref(data, constant))


def test_kernel_and_cycle_sim_agree_on_16kb_use_case():
    """The Pallas modules produce the exact §V-C data path output."""
    from repro.core.hw.system import ElasticUseCase
    uc = ElasticUseCase()
    res = uc.run_case(3)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 1 << 26, size=uc.n_words, dtype=np.uint32)
    x = multiply_const(jnp.asarray(data), uc.constant)
    x = hamming_encode(x)
    x, _ = hamming_decode(x)
    np.testing.assert_array_equal(np.asarray(x), res.output)
