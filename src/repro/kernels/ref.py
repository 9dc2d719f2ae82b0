"""Pure-jnp oracles for every Pallas kernel (self-contained, no Pallas).

These are the correctness references the kernel sweep tests
``assert_allclose`` against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: [B,H,Sq,D]; k/v: [B,Hkv,Sk,D] (GQA by head grouping)."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# paged attention (decode)
# ---------------------------------------------------------------------------


def paged_attention_ref(q, k_pages, v_pages, page_table, lengths):
    """Decode attention over a paged KV cache.

    q: [B, H, D] (one new token per sequence)
    k_pages/v_pages: [n_pages, page, Hkv, D]  (the "flash" pool)
    page_table: [B, pages_per_seq] int32 physical page ids
    lengths: [B] int32 valid token counts
    Returns [B, H, D].
    """
    b, h, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    g = h // hkv
    # gather logical KV [B, S, Hkv, D]
    k = k_pages[page_table].reshape(b, pages_per_seq * page, hkv, d)
    v = v_pages[page_table].reshape(b, pages_per_seq * page, hkv, d)
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    valid = jnp.arange(pages_per_seq * page)[None] < lengths[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(q.dtype), v)
    return out.reshape(b, h, d)


def paged_attention_q8_ref(q, k_pages, v_pages, k_scale, v_scale,
                           page_table, lengths):
    """Oracle for the int8-KV paged kernel: dequantize, then the fp ref."""
    kd = k_pages.astype(jnp.float32) * k_scale[..., None]
    vd = v_pages.astype(jnp.float32) * v_scale[..., None]
    return paged_attention_ref(q, kd.astype(q.dtype), vd.astype(q.dtype),
                               page_table, lengths)


# ---------------------------------------------------------------------------
# embedding-bag aggregation (DLRM 'embed' workload)
# ---------------------------------------------------------------------------


def embed_agg_ref(table, indices, weights=None):
    """Sum-pool embedding lookups (sparse-feature aggregation).

    table: [V, D]; indices: [B, L] int32; weights: optional [B, L].
    Returns [B, D] = sum_l w[b,l] * table[indices[b,l]].
    """
    rows = table[indices]                        # [B, L, D]
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    return jnp.sum(rows, axis=1)


# ---------------------------------------------------------------------------
# paged scan/filter/reduce (in-storage analytics)
# ---------------------------------------------------------------------------


def _host_pages(data, page_rows: int):
    """The extent as f32 pages [n_pages, page_rows, n_cols] (rows past
    the end zero-padded) plus its page count."""
    n_rows, n_cols = data.shape
    n_pages = -(-max(n_rows, 1) // page_rows)
    pad = n_pages * page_rows - n_rows
    blocks = jnp.pad(data.astype(jnp.float32), ((0, pad), (0, 0))
                     ).reshape(n_pages, page_rows, n_cols)
    return blocks, n_pages


def scan_filter_reduce_ref(data, page_rows: int, threshold=0.0, *,
                           filter_col: int = 0, filter_op: str = "all"):
    """Host-side reference for ``kernels.isp_scan.scan_filter_reduce``.

    data: [n_rows, n_cols] — the extent the host read back in full
    (the "host reads everything" baseline).  The fold walks pages of
    ``page_rows`` rows sequentially through the kernel's *own* page
    fold (``fold_page`` / ``merge_partials``), so the result is
    bit-identical to the in-storage path — the offload correctness
    contract.
    Returns [8, n_cols] float32 (count/sum/min/max rows, zero padding).
    """
    from repro.kernels.isp_scan import (NEG_INF, POS_INF, REDUCE_ROWS,
                                        fold_page, merge_partials)
    n_rows, n_cols = data.shape
    blocks, n_pages = _host_pages(data, page_rows)
    thresh = jnp.asarray(threshold, jnp.float32).reshape(())

    def fold(acc, xs):
        pi, block = xs
        part = fold_page(block, pi, n_rows, thresh, page_rows=page_rows,
                         filter_col=filter_col, filter_op=filter_op)
        return merge_partials(acc, part), None

    row = jnp.zeros((1, n_cols), jnp.float32)
    init = (row, row, row + POS_INF, row + NEG_INF)
    acc, _ = lax.scan(fold, init,
                      (jnp.arange(n_pages, dtype=jnp.int32), blocks))
    pad = jnp.zeros((REDUCE_ROWS - 4, n_cols), jnp.float32)
    return jnp.concatenate(list(acc) + [pad], axis=0)


def topk_scan_ref(data, query, *, page_rows: int, k: int,
                  metric: str = "dot"):
    """Host-side reference for ``kernels.isp_scan.topk_scan``.

    data: [n_rows, n_cols] — the extent the host read back in full.
    The fold walks pages sequentially and calls the kernel's *own*
    page fold (``topk_fold_page``), so scores, merge order, and
    tie-breaking (smallest row id wins equal scores) are bit-identical
    to the in-storage path by construction.
    Returns [8, topk_pad(k)] float32 (scores row 0, f32 ids row 1).
    """
    from repro.kernels.isp_scan import topk_block, topk_fold_page, topk_init
    n_rows, n_cols = data.shape
    blocks, n_pages = _host_pages(data, page_rows)
    q = jnp.asarray(query, jnp.float32).reshape(1, n_cols)

    def fold(carry, xs):
        acc_s, acc_i = carry
        pi, block = xs
        return topk_fold_page(block, pi, n_rows, q, acc_s, acc_i,
                              page_rows=page_rows, k=k,
                              metric=metric), None

    (acc_s, acc_i), _ = lax.scan(
        fold, topk_init(k), (jnp.arange(n_pages, dtype=jnp.int32), blocks))
    return topk_block(acc_s, acc_i, k=k)


# ---------------------------------------------------------------------------
# rwkv6 wkv chunked recurrence
# ---------------------------------------------------------------------------


def wkv_ref(r, k, v, logw, u, s0):
    """Naive per-token scan.  r/k/logw: [B,S,H,dk]; v: [B,S,H,dv];
    u: [H,dk]; s0: [B,H,dk,dv].  Returns (o [B,S,H,dv], sT)."""
    def step(state, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", rt, state + u[None, :, :, None] * kv)
        state = jnp.exp(wt)[..., None] * state + kv
        return state, o
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (r, k, v, logw))
    sT, os = lax.scan(step, s0, xs)
    return jnp.moveaxis(os, 0, 1), sT
