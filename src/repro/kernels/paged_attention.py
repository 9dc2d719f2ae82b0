"""Paged decode attention — Pallas TPU kernel.

This is the TPU-native analogue of DockerSSD's in-storage KV processing:
the KV cache lives in fixed-size *pages* (flash blocks -> HBM pages), a
page table maps each sequence's logical extent to physical pages, and
the kernel streams pages HBM->VMEM via scalar-prefetch index maps,
accumulating an online softmax *at the page* — compute moves to the
data, the data never moves to the query.

Blocks: a block is ``pages_per_block`` pages of one row (a power of two,
about ``BLOCK_TOKENS`` tokens, never wider than the table), and the
table is padded with not-owned entries to whole blocks.  The grid is one
sequential axis of steps over the blocks that hold tokens of their row,
row after row (an empty row gets one step, to write its zeros): its
length is worked out on the device from the row lengths, so no grid step
is spent on a block past a row's length.  The per-row accumulators
persist in VMEM scratch across a row's steps.  A step takes the block's
pages as that many block inputs per array, each one physical page with
**all** its KV heads — ``(page, Hkv, D)`` spans the array's last two
dims, which is what the TPU's (8, 128) block tiling rule accepts for any
head count, head dim, page size and dtype — and the pipeline copies the
next step's pages while this one computes, across row boundaries too.
(A copy the kernel issued itself would have to slice the lane-padded
64-wide head dim, which Mosaic refuses.)  The step reads its pages as
rows of (token, KV head) — each page cast to f32 whole, no head gathered
out of it — makes one score product ``[H, D] x [D, rows]`` for all query
heads, masks each query head to the rows of its own KV head (their
weights are exact zeros), makes one value product ``[H, rows] x [rows,
D]``, and updates the online softmax once.  Per token that is about an
eighth of the vector work of scoring each KV head's rows apart.

Skipping: a block whose pages this node owns none of does no compute;
in a block the tokens past the length, and the pages whose table entry
is negative (not owned by this pool node — the distributed contract
below), are masked.  A page input that holds no owned tokens of its row
names the page it fetched at its last step that did, so the pipeline,
seeing the same block index again, copies nothing: traffic scales with
actual context length, not table capacity.  The wrapper works out the
steps, the page every input fetches and whether the row owns it, and
whether a step's block holds owned or not-owned tokens, into prefetched
tables, so an index map is one load and a shift and a step reads its
flags from one word.

``return_stats=True`` also returns the online-softmax statistics (row
max ``m`` and denominator ``l``) next to the normalized output: the
partial form pool nodes merge across the mesh
(``runtime.serve.combine_partials``).

Calling convention: the batched serving path holds *stacked* pages
``[n_layers, hbm_pages, page, Hkv, D]`` (core.kv_tier.PageStore) and
carries them whole through its jitted ``lax.scan`` over layers.  Each
layer step passes the whole stacked array plus its layer index, a
scalar-prefetch operand: each page input's index map returns ``(layer,
page, 0, 0, 0)``, so a grid step copies its block's pages of one layer
and no per-layer slice of the store is ever materialized.
The 4-D form ``paged_attention(q, k_pages, v_pages, table, lengths)``
is the one-layer case of the same call (a leading-axis reshape and
layer 0).  ``paged_attention`` is safe to trace inside an enclosing
jit (runtime/serve.py fuses append-scatter + attention + FFN into one
step).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: tokens one grid step covers, at most (see ``pages_per_block``)
BLOCK_TOKENS = 256


def pages_per_block(pps: int, page: int) -> int:
    """Pages one grid step covers for a table ``pps`` pages wide: the
    largest power of two whose tokens fit ``BLOCK_TOKENS``, never more
    than the table width, at least one."""
    def floor2(n):
        return 1 << (max(n, 1).bit_length() - 1)
    return min(floor2(BLOCK_TOKENS // page), floor2(pps))


def _paged_kernel(pt_ref, len_ref, li_ref, row_ref, blk_ref, q_ref, *refs,
                  page: int, ppb: int, sm_scale: float, nh: int, hkv: int,
                  quantized: bool, stats: bool):
    n_src = 4 if quantized else 2
    refs = list(refs)
    # one [page, Hkv, D] (scales [page, Hkv]) block per page of the grid
    # step, per source: K, V (and their scales)
    pages = [refs[n * ppb:(n + 1) * ppb] for n in range(n_src)]
    refs = refs[n_src * ppb:]
    o_ref = refs.pop(0)
    mo_ref, lo_ref = (refs.pop(0), refs.pop(0)) if stats else (None, None)
    acc_ref, m_ref, l_ref = refs
    step = pl.program_id(0)
    b = row_ref[step]
    flags = blk_ref[step]
    j = flags >> 3                              # the row's block
    length = len_ref[b]
    first = step * ppb                          # the step's first entry

    def owned(i):
        # the low bit of the step's entry i (the wrapper's table): the
        # row's table names a page there, not a negative (not-owned) id
        return (pt_ref[first + i] & 1) == 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend():
        # the block as rows (token, KV head), the head minor: every query
        # head scores every row, and a row counts for the query heads of
        # its own KV head only (exact zeros elsewhere), so no head is
        # gathered out of the pages
        t = ppb * page * hkv
        col = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        own = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (nh, 1), 0),
                          nh // hkv)
        live = ((jax.lax.div(col, hkv) < length - j * (ppb * page)) &
                (jax.lax.rem(col, hkv) == own)).astype(jnp.int32)  # [H, t]

        def owned_only():
            def drop(i, keep):
                theirs = (col >= i * (page * hkv)) & \
                    (col < (i + 1) * (page * hkv)) & ~owned(i)
                return jnp.where(theirs, 0, keep)
            return jax.lax.fori_loop(0, ppb, drop, live)
        # the step's bit 2: the block holds tokens in pages not owned
        live = jax.lax.cond((flags & 4) != 0, owned_only, lambda: live) > 0

        def rows(n):
            # [t, D]: every page of the block, a row per (token, KV head)
            out = []
            for i, r in enumerate(pages[n]):
                x = r[...].astype(jnp.float32)               # [page, Hkv, D]
                if quantized:
                    # int8/fp8 codes stream HBM->VMEM and dequantize
                    # in-register: HBM traffic is the quantized bytes.
                    # The page's scales [page, Hkv] turn to a column per
                    # token, [Hkv, 1], beside that token's rows
                    sc = pages[n + 2][i][...].T                # [Hkv, page]
                    x = x * jnp.stack([sc[:, u:u + 1] for u in range(page)])
                out.append(x.reshape(page * hkv, -1))
            return jnp.concatenate(out, axis=0)

        k, v = rows(0), rows(1)
        q = q_ref[0].astype(jnp.float32)                         # [H, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(live, s * sm_scale, NEG_INF)               # [H, t]
        m_prev = m_ref[...]                                      # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # the step's bit 1: the block holds tokens in pages this node owns
    pl.when((flags & 2) != 0)(attend)

    @pl.when((flags & 1) == 1)                  # the row's last step
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if stats:
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def _step_tables(page_table, lengths, page: int, ppb: int):
    """The grid's steps, worked out on the device from the row lengths.

    The grid steps only through blocks that hold tokens of their row,
    row after row (an empty row gets one step, to write its zeros).
    Returns ``(table, row, blk, n_steps)``, each sized for a full grid of
    ``b x n_blocks`` steps, its tail unused: step ``t`` is block ``blk[t]
    >> 3`` of row ``row[t]``, whose low bits mark the row's last step
    (1), a block holding tokens in pages the row owns (2) and in pages it
    does not (4).  ``table[t * ppb + i]`` (flat) is ``fetch * 2 + owned`` for
    the step's page input ``i``: the page it fetches is its own where the
    row owns a page with tokens there, else the one it fetched at its
    last such step, so the pipeline, seeing the same block again, copies
    nothing (before any such step, page 0); the kernel reads the low bit,
    the index map the rest.

    Written in ``lax``: every program that calls the kernel traces this,
    and a jnp operator or index traces a jitted function of its own
    (about 1 ms each; 28 ms for these tables against 6.5, on a CPU host).
    """
    lax = jax.lax
    b, w = page_table.shape
    n_blocks = w // ppb
    s = b * n_blocks

    def down(x, n):
        # [m] -> [m, n], x down the rows
        return lax.broadcast_in_dim(x, (x.shape[0], n), (0,))

    def take(x, idx):
        # x.reshape(-1)[idx]
        return lax.gather(
            lax.reshape(x, (x.size,)), lax.expand_dims(idx, (idx.ndim,)),
            lax.GatherDimensionNumbers(offset_dims=(),
                                       collapsed_slice_dims=(0,),
                                       start_index_map=(0,)),
            (1,), mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    def i32(x):
        return lax.convert_element_type(x, jnp.int32)

    live_pages = lax.div(lax.add(lengths, page - 1), page)
    nb = lax.clamp(1, lax.div(lax.add(live_pages, ppb - 1), ppb), n_blocks)
    end = lax.cumsum(nb)
    t = lax.iota(jnp.int32, s)
    ends = lax.broadcast_in_dim(end, (s, b), (1,))
    done = lax.le(ends, down(t, b))             # [s, b] rows ended by step t
    row = lax.min(lax.reduce_sum(i32(done), (1,)), b - 1)
    j = lax.min(lax.sub(t, lax.reduce_max(
        lax.select(done, ends, lax.full_like(ends, 0)), (1,))), n_blocks - 1)
    pos = lax.broadcasted_iota(jnp.int32, (s, ppb), 1)
    slot = lax.add(down(lax.mul(j, ppb), ppb), pos)            # [s, ppb]
    entry = take(page_table, lax.add(down(lax.mul(row, w), ppb), slot))
    inside = lax.lt(slot, down(take(live_pages, row), ppb))
    mine = lax.ge(entry, 0)
    fresh = lax.bitwise_and(inside, mine)
    last = lax.cummax(lax.select(fresh, down(t, ppb), lax.full_like(pos, 0)),
                      axis=0)
    fetch = lax.max(take(entry, lax.add(lax.mul(last, ppb), pos)), 0)
    table = lax.reshape(lax.add(lax.mul(fetch, 2), i32(mine)), (s * ppb,))
    foreign = lax.reduce_or(lax.bitwise_and(inside, lax.bitwise_not(mine)),
                            (1,))
    blk = lax.add(lax.add(lax.mul(j, 8), lax.mul(i32(foreign), 4)),
                  lax.add(lax.mul(i32(lax.reduce_or(fresh, (1,))), 2),
                          i32(lax.eq(j, lax.sub(take(nb, row), 1)))))
    return table, row, blk, lax.reduce_sum(nb, (0,))


def _paged_call(q, k_pages, v_pages, scales, page_table, lengths, layer, *,
                interpret: bool, return_stats: bool, name: str):
    """The one kernel call.  Pages are either stacked ``[L, P, page,
    Hkv, D]`` (scales ``[L, P, page, Hkv]``) read at ``layer``, or one
    layer's ``[P, page, Hkv, D]`` when ``layer`` is None."""
    if layer is None:
        k_pages, v_pages = k_pages[None], v_pages[None]
        scales = tuple(s[None] for s in scales)
        layer = 0
    b, h, d = q.shape
    _, _, page, hkv, _ = k_pages.shape
    pps = page_table.shape[1]
    ppb = pages_per_block(pps, page)
    n_blocks = -(-pps // ppb)
    w = n_blocks * ppb
    if w != pps:
        # pad the table to whole blocks with not-owned entries
        page_table = jnp.pad(page_table, ((0, 0), (0, w - pps)),
                             constant_values=-1)
    table, row, blk, n_steps = _step_tables(page_table, lengths, page, ppb)
    srcs = [k_pages, v_pages] + [s.astype(jnp.float32) for s in scales]
    kernel = functools.partial(
        _paged_kernel, page=page, ppb=ppb, sm_scale=1.0 / math.sqrt(d),
        nh=h, hkv=hkv, quantized=bool(scales), stats=return_stats)

    def page_spec(i, tail):
        def index(t, tab, ln, li, rw, bk):
            # page i of step t (``fetch`` above), at the prefetched
            # layer.  The pipeline evaluates this for every page of every
            # grid step, so it is a load and a shift, in lax alone (a jnp
            # operator traces a jitted function, for each page input)
            s = jax.lax.add(jax.lax.mul(t, ppb), i)
            return ((li[0], jax.lax.shift_right_logical(tab[s], 1), 0)
                    + (0,) * len(tail))
        return pl.BlockSpec((pl.squeezed, pl.squeezed, page) + tail, index)

    def head_spec(last):
        return pl.BlockSpec((1, h, last),
                            lambda t, pt, ln, li, rw, bk: (rw[t], 0, 0))

    out_specs = [head_spec(d)]
    out_shape = [jax.ShapeDtypeStruct((b, h, d), q.dtype)]
    if return_stats:
        out_specs += [head_spec(1), head_spec(1)]
        out_shape += [jax.ShapeDtypeStruct((b, h, 1), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=[head_spec(d)] + [page_spec(i, a.shape[3:])
                                   for a in srcs for i in range(ppb)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(table, lengths, jnp.reshape(layer, (1,)).astype(jnp.int32), row, blk,
      q, *[a for a in srcs for _ in range(ppb)])
    if not return_stats:
        return out[0].reshape(b, h, d)
    o, m, l = out
    return o.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def paged_attention_q8(q, k_pages, v_pages, k_scale, v_scale, page_table,
                       lengths, *, layer=None, interpret: bool = False,
                       return_stats: bool = False):
    """Quantized-KV paged decode attention (int8 or fp8 codes).

    q: [B, H, D] float; k_pages/v_pages: codes [n_pages, page, Hkv, D],
    or stacked [n_layers, n_pages, page, Hkv, D] read at ``layer`` (an
    int32 scalar, traced or not); k_scale/v_scale: f32 [n_pages, page,
    Hkv] (stacked: [n_layers, n_pages, page, Hkv]); page_table: [B, pps]
    int32 (negative = not owned, skipped); lengths: [B].  Returns
    [B, H, D], or (o, m [B, H], l [B, H]) with ``return_stats``."""
    return _paged_call(q, k_pages, v_pages, (k_scale, v_scale), page_table,
                       lengths, layer, interpret=interpret,
                       return_stats=return_stats, name="paged_attention_q8")


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    layer=None, interpret: bool = False,
                    return_stats: bool = False):
    """q: [B, H, D]; k_pages/v_pages: [n_pages, page, Hkv, D], or stacked
    [n_layers, n_pages, page, Hkv, D] read at ``layer`` (an int32
    scalar, traced or not); page_table: [B, pages_per_seq] int32
    (negative = not owned, skipped); lengths: [B] int32.  Returns
    [B, H, D], or (o, m [B, H], l [B, H]) with ``return_stats``."""
    return _paged_call(q, k_pages, v_pages, (), page_table, lengths, layer,
                       interpret=interpret, return_stats=return_stats,
                       name="paged_attention")
