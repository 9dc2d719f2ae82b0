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


#: tables wide enough for several of the kernel's blocks per row, (b,
#: page, pps), whose rows take the lengths of ``_lengths`` at its edges
WIDE = {(5, 16, 48), (3, 8, 100)}


def _lengths(key, b, page, pps):
    """[b] row lengths: random in [1, pps * page], or for a ``WIDE``
    table a block edge, mid-block and mid-page, a page edge inside a
    block, a row of length 0 and the full table (``pps`` need not be a
    multiple of the block's pages)."""
    if (b, page, pps) not in WIDE:
        return jax.random.randint(key, (b,), 1, pps * page + 1, jnp.int32)
    from repro.kernels.paged_attention import pages_per_block
    bk = pages_per_block(pps, page) * page
    edges = [bk, bk + bk // 2 + page // 2, bk + 2 * page, 0, pps * page]
    assert max(edges) == pps * page and bk < pps * page
    return jnp.asarray(edges[:b], jnp.int32)


def _assert_rows(out, expect, lens, tol):
    """``out`` matches ``expect`` on rows that hold tokens; a row of
    length 0 attends to nothing and reads 0 (the oracles spread it
    evenly over the table instead)."""
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(expect)[live], atol=tol, rtol=tol)
    assert not np.asarray(out)[~live].any()


@pytest.mark.parametrize("b,h,hkv,d,page,pps,npage", [
    (2, 8, 2, 64, 16, 8, 32),
    (4, 4, 4, 128, 32, 4, 16),
    (2, 8, 1, 64, 16, 6, 12),   # MQA
    (1, 16, 8, 128, 8, 16, 16),
    (5, 8, 2, 64, 16, 48, 240),     # 3 blocks a row
    (3, 4, 4, 128, 8, 100, 300),    # 4 blocks, the last one padded
])
@pytest.mark.parametrize("stacked", [None, "bf16", "int8"])
def test_paged_attention(b, h, hkv, d, page, pps, npage, stacked):
    """The 4-D call matches its oracle.  ``stacked``: the layer-indexed
    call on stacked ``[L, P, page, Hkv, D]`` pages (the serving steps'
    form) equals the 4-D call on slice ``[li]``, for the first, a middle
    and the last layer, with not-owned (negative) table entries inside
    live blocks and stats."""
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    pt = jax.random.permutation(ks[3], npage)[:b * pps].reshape(
        b, pps).astype(jnp.int32)
    lens = _lengths(ks[4], b, page, pps)
    if stacked is None:
        kp = jax.random.normal(ks[1], (npage, page, hkv, d), jnp.float32)
        vp = jax.random.normal(ks[2], (npage, page, hkv, d), jnp.float32)
        out = ops.paged_attention(q, kp, vp, pt, lens)
        expect = ref.paged_attention_ref(q, kp, vp, pt, lens)
        _assert_rows(out, expect, lens, 2e-5)
        return
    from repro.kernels.paged_attention import (paged_attention as pa,
                                               paged_attention_q8 as pa8)
    from repro.models.layers import quantize_kv
    n_layers = 3
    shape = (n_layers, npage, page, hkv, d)
    kp = jax.random.normal(ks[1], shape, jnp.float32)
    vp = jax.random.normal(ks[2], shape, jnp.float32)
    table = jnp.where(jnp.arange(pps)[None, :] % 3 == 1, -1, pt)
    if stacked == "int8":
        (kq, ksc), (vq, vsc) = quantize_kv(kp), quantize_kv(vp)

        def call(li, *pages):
            return pa8(q, *pages, table, lens, layer=li, interpret=True,
                       return_stats=True)
        pages = (kq, vq, ksc, vsc)
    else:
        def call(li, *pages):
            return pa(q.astype(jnp.bfloat16), *pages, table, lens,
                      layer=li, interpret=True, return_stats=True)
        pages = (kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16))
    for li in (0, 1, n_layers - 1):
        got = call(jnp.int32(li), *pages)
        want = call(None, *(p[li] for p in pages))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


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
    (5, 8, 2, 64, 16, 48, 240),     # 3 blocks a row
    (3, 4, 4, 128, 8, 100, 300),    # 4 blocks, the last one padded
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
    lens = _lengths(ks[4], b, page, pps)
    out = ops.paged_attention_q8(q, kq, vq, ksc, vsc, pt, lens)
    oracle = ref.paged_attention_q8_ref(q, kq, vq, ksc, vsc, pt, lens)
    _assert_rows(out, oracle, lens, 2e-5)
    fp = ref.paged_attention_ref(q, kp_f, vp_f, pt, lens)
    live = np.asarray(lens) > 0
    # quantization noise only
    assert float(jnp.abs(out - fp)[live].max()) < 0.05


@pytest.mark.parametrize("pps", [4, 40])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_stats_match_partial_contract(quantized, pps):
    """``return_stats`` yields the ``(o, m, l)`` partial contract of
    ``runtime.serve.paged_attention_partial`` (the pool's per-node
    attention), and a negative page id is skipped as not owned.  At 40
    pages (3 blocks of the kernel, the last padded) the rows end on a
    block edge, mid-block and mid-page, and one is empty."""
    from repro.kernels.paged_attention import (paged_attention as pa,
                                               paged_attention_q8 as pa8,
                                               pages_per_block)
    from repro.models.layers import quantize_kv
    from repro.runtime.serve import paged_attention_partial
    h, hkv, d, page = 8, 2, 64, 16
    stripe = [i % 2 == 0 for i in range(pps)]
    if pps == 4:
        lens = [pps * page, 37, 20]
        # row 0 owns every page, row 1 a striped subset, row 2 none
        owned = [[True] * pps, stripe, [False] * pps]
    else:
        bk = pages_per_block(pps, page) * page
        lens = [bk, bk + bk // 2 + 3, 0, pps * page, 2 * bk - 8]
        # rows 1 and 3 own every other page; row 4 all but the one at
        # its second block's start
        owned = [[True] * pps, stripe, [True] * pps, stripe,
                 [i != bk // page for i in range(pps)]]
    b = len(lens)
    npage = b * pps
    ks = jax.random.split(KEY, 6)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npage, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npage, page, hkv, d), jnp.float32)
    pt = jax.random.permutation(ks[3], npage)[:b * pps].reshape(
        b, pps).astype(jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    owned = jnp.asarray(owned)
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
