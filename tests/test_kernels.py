"""Pallas kernel sweeps: assert_allclose against the pure-jnp oracles
(interpret=True on CPU; native compile on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 4, 2, 256, 64),
    (1, 8, 1, 128, 128),       # MQA
    (2, 4, 4, 384, 64),        # MHA
    (1, 2, 1, 512, 256),       # gemma-style wide heads
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, hkv, s, d, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,h,hkv,d,page,pps,npage", [
    (2, 8, 2, 64, 16, 8, 32),
    (4, 4, 4, 128, 32, 4, 16),
    (2, 8, 1, 64, 16, 6, 12),   # MQA
    (1, 16, 8, 128, 8, 16, 16),
])
def test_paged_attention(b, h, hkv, d, page, pps, npage):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npage, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npage, page, hkv, d), jnp.float32)
    pt = jax.random.permutation(ks[3], npage)[:b * pps].reshape(
        b, pps).astype(jnp.int32)
    lens = jax.random.randint(ks[4], (b,), 1, pps * page + 1, jnp.int32)
    out = ops.paged_attention(q, kp, vp, pt, lens)
    expect = ref.paged_attention_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_length_masking():
    """Tokens beyond `lengths` must not affect the output."""
    ks = jax.random.split(KEY, 4)
    b, h, hkv, d, page, pps, npage = 2, 4, 2, 64, 16, 4, 8
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (npage, page, hkv, d))
    vp = jax.random.normal(ks[2], (npage, page, hkv, d))
    pt = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    lens = jnp.asarray([17, 33], jnp.int32)
    out1 = ops.paged_attention(q, kp, vp, pt, lens)
    kp2 = kp.at[pt[0, 2]].set(999.0)  # beyond length of seq 0
    out2 = ops.paged_attention(q, kp2, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out2[0]),
                               atol=1e-6)


@pytest.mark.parametrize("v,d,b,l", [(1000, 128, 4, 16), (512, 256, 2, 8),
                                     (64, 512, 8, 4)])
@pytest.mark.parametrize("weighted", [False, True])
def test_embed_agg(v, d, b, l, weighted):
    ks = jax.random.split(KEY, 3)
    table = jax.random.normal(ks[0], (v, d))
    idx = jax.random.randint(ks[1], (b, l), 0, v, jnp.int32)
    w = jax.random.normal(ks[2], (b, l)) if weighted else None
    out = ops.embed_agg(table, idx, w)
    expect = ref.embed_agg_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("b,s,h,dk,dv,chunk", [
    (2, 64, 3, 16, 16, 16),
    (1, 128, 2, 32, 32, 32),
    (2, 96, 1, 64, 64, 32),
])
def test_rwkv_scan(b, s, h, dk, dv, chunk):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, dk)))
    u = jax.random.normal(ks[4], (h, dk))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    o, sT = ops.rwkv_scan(r, k, v, logw, u, s0, chunk=chunk)
    o_r, sT_r = ref.wkv_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_r),
                               atol=2e-4, rtol=2e-3)


def test_rwkv_scan_matches_model_chunked():
    """The Pallas kernel and the model's jnp chunked form agree."""
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(KEY, 6)
    b, s, h, dk = 2, 64, 2, 16
    r = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    v = jax.random.normal(ks[2], (b, s, h, dk))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, dk)))
    u = jax.random.normal(ks[4], (h, dk))
    s0 = jax.random.normal(ks[5], (b, h, dk, dk))
    o1, s1 = ops.rwkv_scan(r, k, v, logw, u, s0, chunk=16)
    o2, s2 = wkv_chunked(r, k, v, logw, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4,
                               rtol=1e-3)


@pytest.mark.parametrize("b,h,hkv,d,page,pps,npage", [
    (2, 8, 2, 64, 16, 8, 32),
    (4, 4, 4, 128, 32, 4, 16),
])
def test_paged_attention_q8(b, h, hkv, d, page, pps, npage):
    """int8-KV paged kernel (the §Perf opt-2 realization): matches its
    dequantize-then-attend oracle exactly, and the fp kernel closely."""
    from repro.models.layers import quantize_kv
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp_f = jax.random.normal(ks[1], (npage, page, hkv, d), jnp.float32)
    vp_f = jax.random.normal(ks[2], (npage, page, hkv, d), jnp.float32)
    kq, ksc = quantize_kv(kp_f)
    vq, vsc = quantize_kv(vp_f)
    pt = jax.random.permutation(ks[3], npage)[:b * pps].reshape(
        b, pps).astype(jnp.int32)
    lens = jax.random.randint(ks[4], (b,), 1, pps * page + 1, jnp.int32)
    out = ops.paged_attention_q8(q, kq, vq, ksc, vsc, pt, lens)
    oracle = ref.paged_attention_q8_ref(q, kq, vq, ksc, vsc, pt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)
    fp = ref.paged_attention_ref(q, kp_f, vp_f, pt, lens)
    assert float(jnp.abs(out - fp).max()) < 0.05   # quantization noise only


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_stats_match_partial_contract(quantized):
    """``return_stats`` yields the ``(o, m, l)`` partial contract of
    ``runtime.serve.paged_attention_partial`` (the pool's per-node
    attention), and a negative page id is skipped as not owned."""
    from repro.kernels.paged_attention import (paged_attention as pa,
                                               paged_attention_q8 as pa8)
    from repro.models.layers import quantize_kv
    from repro.runtime.serve import paged_attention_partial
    b, h, hkv, d, page, pps, npage = 3, 8, 2, 64, 16, 4, 16
    ks = jax.random.split(KEY, 6)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npage, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npage, page, hkv, d), jnp.float32)
    pt = jax.random.permutation(ks[3], npage)[:b * pps].reshape(
        b, pps).astype(jnp.int32)
    lens = jnp.asarray([pps * page, 37, 20], jnp.int32)
    # row 0 owns every page, row 1 a striped subset, row 2 none
    owned = jnp.asarray([[True] * pps, [True, False, True, False],
                         [False] * pps])
    table = jnp.where(owned, pt, -1)
    if quantized:
        kq, ksc = quantize_kv(kp)
        vq, vsc = quantize_kv(vp)
        got = pa8(q, kq, vq, ksc, vsc, table, lens, interpret=True,
                  return_stats=True)
        want = paged_attention_partial(q, kq, vq, pt, owned, lens,
                                       k_scale=ksc, v_scale=vsc)
    else:
        got = pa(q, kp, vp, table, lens, interpret=True, return_stats=True)
        want = paged_attention_partial(q, kp, vp, pt, owned, lens)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(got[0][2]).max()) == 0.0    # nothing owned
