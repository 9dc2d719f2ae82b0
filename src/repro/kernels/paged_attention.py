"""Paged decode attention — Pallas TPU kernel.

This is the TPU-native analogue of DockerSSD's in-storage KV processing:
the KV cache lives in fixed-size *pages* (flash blocks -> HBM pages), a
page table maps each sequence's logical extent to physical pages, and
the kernel streams pages HBM->VMEM via scalar-prefetch index maps,
accumulating an online softmax *at the page* — compute moves to the
data, the data never moves to the query.

Grid: (batch, pages_per_seq); the page axis is sequential so the
per-sequence accumulators persist in VMEM scratch.  One grid step DMAs
one physical page with **all** its KV heads — the block ``(1, page,
Hkv, D)`` spans the array's last two dims, which is what the TPU's
(8, 128) block tiling rule accepts for any head count, head dim, page
size and dtype — and the kernel loops over the heads in VMEM.  Pages
whose start offset is beyond the sequence length are skipped (pl.when),
so compute scales with actual context length, not table capacity; so
are pages whose table entry is negative (not owned by this pool node —
the distributed contract below).

``return_stats=True`` also returns the online-softmax statistics (row
max ``m`` and denominator ``l``) next to the normalized output: the
partial form pool nodes merge across the mesh
(``runtime.serve.combine_partials``).

Calling convention: the batched serving path holds *stacked* pages
``[n_layers, hbm_pages, page, Hkv, D]`` (core.kv_tier.PageStore) and
calls this kernel once per layer from inside a jitted ``lax.scan`` over
layers — each scan step feeds the layer's ``[hbm_pages, page, Hkv, D]``
slice plus the (shared) page-table row block.  The kernel itself is
layer-agnostic; ``paged_attention`` below is safe to trace inside an
enclosing jit (runtime/serve.py fuses append-scatter + attention + FFN
into one step).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *refs, page: int,
                  n_pages_per_seq: int, sm_scale: float, hkv: int,
                  quantized: bool, stats: bool):
    refs = list(refs)
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if quantized else (None,
                                                                   None)
    o_ref = refs.pop(0)
    mo_ref, lo_ref = (refs.pop(0), refs.pop(0)) if stats else (None, None)
    acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    pi = pl.program_id(1)
    length = len_ref[b]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((pi * page < length) & (pt_ref[b, pi] >= 0))
    def _body():
        pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        live = pos < length                                  # [1, page]
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)              # [G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)        # [page, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                # int8/fp8 codes stream HBM->VMEM and dequantize
                # in-register: HBM traffic is the quantized bytes
                k = k * ks_ref[0, :, h:h + 1]                # [page, 1]
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * sm_scale, NEG_INF)       # [G, page]
            m_prev = m_ref[h]                                # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pi == n_pages_per_seq - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if stats:
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def _paged_call(q, k_pages, v_pages, scales, page_table, lengths, *,
                interpret: bool, return_stats: bool, name: str):
    b, h, d = q.shape
    _, page, hkv, _ = k_pages.shape
    pps = page_table.shape[1]
    g = h // hkv
    kernel = functools.partial(
        _paged_kernel, page=page, n_pages_per_seq=pps,
        sm_scale=1.0 / math.sqrt(d), hkv=hkv, quantized=bool(scales),
        stats=return_stats)

    def page_spec(*tail):
        # physical page id comes from the prefetched page table (a
        # skipped, not-owned page still names a valid block); the block
        # spans every KV head of the page
        return pl.BlockSpec(
            (1, page) + tail,
            lambda bb, pi, pt, ln: (jnp.maximum(pt[bb, pi], 0), 0) +
            (0,) * len(tail))

    def head_spec(last):
        return pl.BlockSpec((1, hkv, g, last),
                            lambda bb, pi, pt, ln: (bb, 0, 0, 0))

    out_specs = [head_spec(d)]
    out_shape = [jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype)]
    if return_stats:
        out_specs += [head_spec(1), head_spec(1)]
        out_shape += [jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pps),
        in_specs=[head_spec(d), page_spec(hkv, d), page_spec(hkv, d)] +
                 [page_spec(hkv) for _ in scales],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(page_table, lengths, q.reshape(b, hkv, g, d), k_pages, v_pages,
      *[s.astype(jnp.float32) for s in scales])
    if not return_stats:
        return out[0].reshape(b, h, d)
    o, m, l = out
    return o.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def paged_attention_q8(q, k_pages, v_pages, k_scale, v_scale, page_table,
                       lengths, *, interpret: bool = False,
                       return_stats: bool = False):
    """Quantized-KV paged decode attention (int8 or fp8 codes).

    q: [B, H, D] float; k_pages/v_pages: codes [n_pages, page, Hkv, D];
    k_scale/v_scale: f32 [n_pages, page, Hkv]; page_table: [B, pps] int32
    (negative = not owned, skipped); lengths: [B].  Returns [B, H, D],
    or (o, m [B, H], l [B, H]) with ``return_stats``."""
    return _paged_call(q, k_pages, v_pages, (k_scale, v_scale), page_table,
                       lengths, interpret=interpret,
                       return_stats=return_stats, name="paged_attention_q8")


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    interpret: bool = False, return_stats: bool = False):
    """q: [B, H, D]; k_pages/v_pages: [n_pages, page, Hkv, D];
    page_table: [B, pages_per_seq] int32 (negative = not owned, skipped);
    lengths: [B] int32.  Returns [B, H, D], or (o, m [B, H], l [B, H])
    with ``return_stats``."""
    return _paged_call(q, k_pages, v_pages, (), page_table, lengths,
                       interpret=interpret, return_stats=return_stats,
                       name="paged_attention")
