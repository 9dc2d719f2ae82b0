"""Paged scan/filter/reduce — Pallas TPU kernel (in-storage analytics).

The paper's I/O-intensive ISP workloads (pattern find/line/word,
rocksdb read, mariadb TPC-H filters) share one shape: stream the
flash-resident data page by page, apply a row predicate, and fold the
survivors into a tiny aggregate — only the aggregate crosses the wire.
TPU adaptation: analytics extents live as stacked pages
``[n_pages, page_rows, n_cols]`` in HBM ("flash",
core.extent_store.ExtentStore); a per-extent page table arrives via
scalar prefetch and the kernel streams exactly the extent's pages
HBM->VMEM — compute moves to the data, the data never moves to the
host.

**Double-buffered pipeline** (the paper's Virtual-FW prefetch): the
pages stay in HBM (``memory_space=ANY``) and the kernel drives its own
async page copies into a two-slot VMEM buffer — while page *i* is being
reduced, the DMA for page *i+1* is already in flight, so data movement
overlaps compute instead of serializing in front of it.  The loop runs
only over the extent's *valid* pages (``ceil(n_rows / page_rows)``);
pow2 table padding costs neither DMA nor compute.

The aggregate layout (``REDUCE_ROWS`` x n_cols, float32):

  row 0  count of rows passing the filter (broadcast across columns)
  row 1  per-column sum over passing rows
  row 2  per-column min over passing rows (+inf when none pass)
  row 3  per-column max over passing rows (-inf when none pass)
  4..7   zero padding (keeps the output tile-aligned on TPU)

Accumulation is page-sequential in float32 — the pipeline changes only
*when bytes move*, never the fold order or its ops.  Each page's
partials come from one shared function (``fold_page``) whose column
sums are an explicit pairwise tree of f32 adds (``tree_sum_rows``), so
the order is fixed by the code rather than by a compiler's reduction
strategy; ``kernels.ref.scan_filter_reduce_ref`` folds pages through
the same function and stays bit-identical to the in-storage path (the
acceptance contract for offload correctness).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
POS_INF = 1e30

#: filter predicates over the (static) filter column vs the threshold
FILTER_OPS = ("all", "ge", "lt", "eq", "ne")
#: rows of the aggregate output block (see layout above)
REDUCE_ROWS = 8
#: VMEM page slots of the DMA pipeline (2 = classic double buffering)
N_BUFFERS = 2

#: scoring metrics of the top-k scored scan
TOPK_METRICS = ("dot", "cosine")
#: sentinel id of an empty top-k slot (exactly representable in f32,
#: larger than any real row id — the deterministic tie-break loser)
BIG_ID = float(2 ** 30)
#: widest supported k (the merge loop is O(k) per page; the result
#: block must stay tiny — that is the whole wire-reduction story)
MAX_TOPK = 128


def topk_pad(k: int) -> int:
    """Output width of the top-k block: pow2-bucketed, floored at the
    f32 TPU lane tile (128) so the block stays tile-aligned."""
    return max(128, 1 << max(int(k) - 1, 0).bit_length())


def _predicate(key, threshold, op: str):
    if op == "all":
        return jnp.ones_like(key, dtype=jnp.bool_)
    if op == "ge":
        return key >= threshold
    if op == "lt":
        return key < threshold
    if op == "eq":
        return key == threshold
    if op == "ne":
        return key != threshold
    raise ValueError(f"filter_op must be one of {FILTER_OPS}, got {op!r}")


def tree_sum_rows(x):
    """Column sums of ``x`` [R, C] as [1, C], by a pairwise tree of
    elementwise f32 adds (halve the rows while their count is even, then
    add any remaining rows in order).  The add order is explicit, so the
    kernel and the host fold produce the same bits on every backend —
    ``jnp.sum``'s order is whatever the compiler picks for the context."""
    while x.shape[0] > 1 and x.shape[0] % 2 == 0:
        h = x.shape[0] // 2
        x = x[:h] + x[h:]
    s = x[0:1]
    for r in range(1, x.shape[0]):
        s = s + x[r:r + 1]
    return s


def fold_page(block, pi, n_rows, thresh, *, page_rows: int,
              filter_col: int, filter_op: str):
    """One page's f32 partials ``(count, sum, min, max)``, each [1, C]
    (count broadcast across columns) — the shared page fold of the
    kernel and ``kernels.ref.scan_filter_reduce_ref`` (identical ops
    and order: the bit-identity contract lives here)."""
    pos = pi * page_rows + jax.lax.broadcasted_iota(
        jnp.int32, (page_rows, 1), 0)
    key = block[:, filter_col:filter_col + 1]             # [page_rows, 1]
    mask = (pos < n_rows) & _predicate(key, thresh, filter_op)
    cnt = tree_sum_rows(jnp.broadcast_to(mask.astype(jnp.float32),
                                         block.shape))
    total = tree_sum_rows(jnp.where(mask, block, 0.0))
    mn = jnp.min(jnp.where(mask, block, POS_INF), axis=0, keepdims=True)
    mx = jnp.max(jnp.where(mask, block, NEG_INF), axis=0, keepdims=True)
    return cnt, total, mn, mx


def merge_partials(acc, part):
    """Fold one page's partials into the running (count, sum, min, max)."""
    return (acc[0] + part[0], acc[1] + part[1],
            jnp.minimum(acc[2], part[2]), jnp.maximum(acc[3], part[3]))


def _page_loop(pt_ref, nrows_ref, pages_ref, scales_ref, buf_ref, sbuf_ref,
               sem_ref, ssem_ref, on_page, *, page_rows: int, n_pages: int):
    """The double-buffered page pipeline shared by every reducer:
    ``on_page(pi, block)`` folds page ``pi`` ([page_rows, C] f32).

    Quantized code pages ride the page DMA lane and their per-row scale
    pages (1/n_cols the bytes) a second one; dequant happens in VMEM
    right after the copies land — an elementwise f32 multiply, so the
    fold sees exactly the values the host baseline folds, while HBM
    traffic is the quantized bytes."""
    n_rows = nrows_ref[0]
    # valid pages are a prefix of the (pow2-padded) page table; padded
    # entries are never copied nor folded
    n_valid = jnp.minimum(jnp.maximum((n_rows + page_rows - 1) // page_rows,
                                      1), n_pages)

    def copies(slot, idx):
        # one flash page HBM -> VMEM slot, physical id from the
        # scalar-prefetched page table (plus its scale page)
        out = [pltpu.make_async_copy(pages_ref.at[pt_ref[idx]],
                                     buf_ref.at[slot], sem_ref.at[slot])]
        if scales_ref is not None:
            out.append(pltpu.make_async_copy(scales_ref.at[pt_ref[idx]],
                                             sbuf_ref.at[slot],
                                             ssem_ref.at[slot]))
        return out

    # prime the pipeline: page 0's copy starts before any compute
    for c in copies(0, 0):
        c.start()

    def body(pi, _):
        slot = lax.rem(pi, N_BUFFERS)
        nxt = lax.rem(pi + 1, N_BUFFERS)

        # Virtual-FW prefetch: next page's DMA departs while this page
        # is still being reduced
        @pl.when(pi + 1 < n_valid)
        def _prefetch():
            for c in copies(nxt, pi + 1):
                c.start()

        for c in copies(slot, pi):
            c.wait()
        block = buf_ref[slot].astype(jnp.float32)         # [page_rows, C]
        if scales_ref is not None:
            # the scale page lands lane-major [1, page_rows]; its
            # transpose is the per-row column
            block = block * sbuf_ref[slot].T              # [page_rows, 1]
        on_page(pi, block)
        return ()

    lax.fori_loop(0, n_valid, body, ())


def _scan_kernel(pt_ref, nrows_ref, thresh_ref, pages_ref, *refs,
                 page_rows: int, n_pages: int, filter_col: int,
                 filter_op: str, quantized: bool):
    # the quantized variant adds a scale pool operand, a scale double
    # buffer and its DMA semaphores
    if quantized:
        (scales_ref, o_ref, buf_ref, sbuf_ref, sem_ref, ssem_ref,
         *acc_refs) = refs
    else:
        scales_ref = sbuf_ref = ssem_ref = None
        o_ref, buf_ref, sem_ref, *acc_refs = refs
    n_rows = nrows_ref[0]
    cnt_ref, sum_ref, min_ref, max_ref = acc_refs
    cnt_ref[...] = jnp.zeros_like(cnt_ref)
    sum_ref[...] = jnp.zeros_like(sum_ref)
    min_ref[...] = jnp.full_like(min_ref, POS_INF)
    max_ref[...] = jnp.full_like(max_ref, NEG_INF)

    def on_page(pi, block):
        part = fold_page(block, pi, n_rows, thresh_ref[0],
                         page_rows=page_rows, filter_col=filter_col,
                         filter_op=filter_op)
        acc = merge_partials(tuple(r[...] for r in acc_refs), part)
        for r, a in zip(acc_refs, acc):
            r[...] = a

    _page_loop(pt_ref, nrows_ref, pages_ref, scales_ref, buf_ref, sbuf_ref,
               sem_ref, ssem_ref, on_page, page_rows=page_rows,
               n_pages=n_pages)
    o_ref[...] = jnp.zeros_like(o_ref)
    for row, r in enumerate(acc_refs):
        o_ref[row:row + 1, :] = r[...]


def _pipeline_operands(pages, scales):
    """in_specs, operands and DMA scratch of the page pipeline.  The
    page pool (and scale pool) stays in HBM; the kernel's own DMA
    pipeline pulls pages into the double buffer."""
    n_phys, page_rows, n_cols = pages.shape
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [pages]
    scratch = [pltpu.VMEM((N_BUFFERS, page_rows, n_cols), pages.dtype)]
    sems = [pltpu.SemaphoreType.DMA((N_BUFFERS,))]
    if scales is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        # lane-major scale pages: a [page_rows, 1] column page cannot be
        # DMA'd (its lane dim would be 1, not a multiple of 128)
        operands.append(scales.reshape(n_phys, 1, page_rows)
                        .astype(jnp.float32))
        scratch.append(pltpu.VMEM((N_BUFFERS, 1, page_rows), jnp.float32))
        sems.append(pltpu.SemaphoreType.DMA((N_BUFFERS,)))
    return in_specs, operands, scratch + sems


def scan_filter_reduce(pages, page_table, n_rows, threshold, *,
                       scales=None, filter_col: int = 0,
                       filter_op: str = "all", interpret: bool = False):
    """Filtered aggregate over an extent's flash-resident pages.

    pages: [n_phys, page_rows, n_cols] (the whole ExtentStore pool —
    it stays in HBM; the kernel DMAs one page at a time);
    page_table: [pps] int32 physical page ids of this extent (pow2-pad
    with any valid id — padded pages past ``n_rows`` cost nothing);
    n_rows: [1] int32 valid rows; threshold: [1] f32 filter operand.
    ``scales`` ([n_phys, page_rows] f32) marks a quantized pool:
    pages hold codes and the kernel dequantizes each page in VMEM right
    after its DMA lands (bit-identical fold, quantized HBM traffic).
    ``filter_col``/``filter_op`` are static (see FILTER_OPS).
    Returns [REDUCE_ROWS, n_cols] float32 (layout in the module doc).
    """
    if filter_op not in FILTER_OPS:
        raise ValueError(f"filter_op must be one of {FILTER_OPS}, "
                         f"got {filter_op!r}")
    n_phys, page_rows, n_cols = pages.shape
    if not 0 <= filter_col < n_cols:
        raise ValueError(f"filter_col {filter_col} out of range "
                         f"[0, {n_cols})")
    in_specs, operands, scratch = _pipeline_operands(pages, scales)
    # count / sum / min / max rows
    scratch += [pltpu.VMEM((1, n_cols), jnp.float32) for _ in range(4)]
    kernel = functools.partial(
        _scan_kernel, page_rows=page_rows, n_pages=page_table.shape[0],
        filter_col=filter_col, filter_op=filter_op,
        quantized=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((REDUCE_ROWS, n_cols),
                               lambda pi, pt, nr, th: (0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((REDUCE_ROWS, n_cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="scan_filter_reduce" if scales is None
        else "scan_filter_reduce_q",
    )(page_table, n_rows, threshold, *operands)


# ---------------------------------------------------------------------------
# Scored scan / top-k — the retrieval reducer
#
# Same pipeline, different fold: each page's rows are scored against a
# query vector (dot or cosine) and merged into a (score, id)
# accumulator of topk_pad(k) slots, of which the first k are used.  The
# output block is [REDUCE_ROWS, topk_pad(k)] f32:
#
#   row 0  top-k scores, descending (zero past k)
#   row 1  matching row ids, stored as f32 (exact for ids < 2^24);
#          empty slots hold (NEG_INF, BIG_ID)
#   2..7   zero padding (tile alignment)
#
# Ties are deterministic: equal scores rank by *smallest row id*, in
# both the kernel and the reference fold — the merge itself is one
# shared function (`topk_fold_page`), so bit-identity holds by
# construction, not by parallel maintenance.
# ---------------------------------------------------------------------------


def topk_init(k: int):
    """Empty accumulator: [1, topk_pad(k)] rows of (NEG_INF, BIG_ID)."""
    kpad = topk_pad(k)
    return (jnp.full((1, kpad), NEG_INF, jnp.float32),
            jnp.full((1, kpad), BIG_ID, jnp.float32))


def _topk_merge(acc_s, acc_i, cand_s, cand_i, *, k: int):
    """Merge page candidates into the accumulator.

    All inputs are [1, n] f32 rows (the accumulator topk_pad(k) wide).
    k selection passes: take the max remaining score, break ties on the
    smallest id, knock the winner out, repeat; the winner lands in slot
    j through an iota mask (no scatter).  Empty slots carry (NEG_INF,
    BIG_ID) so they lose every comparison and tie-break
    deterministically last.
    """
    c_s = jnp.concatenate([acc_s, cand_s], axis=1)
    c_i = jnp.concatenate([acc_i, cand_i], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)

    def extract(j, carry):
        c_s, c_i, o_s, o_i = carry
        m = jnp.max(c_s)
        cid = jnp.min(jnp.where(c_s == m, c_i, BIG_ID))
        o_s = jnp.where(lane == j, m, o_s)
        o_i = jnp.where(lane == j, cid, o_i)
        # knock the winner out entirely (score AND id) so exhausted
        # slots keep yielding the (NEG_INF, BIG_ID) empty sentinel
        hit = (c_s == m) & (c_i == cid)
        c_s = jnp.where(hit, NEG_INF, c_s)
        c_i = jnp.where(hit, BIG_ID, c_i)
        return c_s, c_i, o_s, o_i

    init = (c_s, c_i) + topk_init(k)
    _, _, o_s, o_i = lax.fori_loop(0, k, extract, init)
    return o_s, o_i


def topk_fold_page(block, pi, n_rows, q, acc_s, acc_i, *,
                   page_rows: int, k: int, metric: str):
    """Score one page's rows and merge them into the accumulator —
    the shared fold of the kernel and ``kernels.ref.topk_scan_ref``
    (identical ops and order: the bit-identity contract lives here).

    Row dots transpose the page and sum its rows with
    :func:`tree_sum_rows` — NOT ``jnp.sum``/``dot_general``, whose
    accumulation order is implementation-defined and comes out
    different per compilation context (kernel vs jitted host fold:
    last-ulp score divergence breaks the ordering).  Explicit f32 adds
    have a defined order the compiler may not reassociate, so both
    compilations produce the same bits.  ``cosine`` divides by the row
    norm only; ranking is invariant to the query's scale, so
    pre-normalize the query for true cosine.
    """
    pos = pi * page_rows + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_rows), 1)
    s = tree_sum_rows((block * q).T)                      # [1, page_rows]
    if metric == "cosine":
        norm = jnp.sqrt(tree_sum_rows((block * block).T))
        s = s / jnp.maximum(norm, 1e-6)
    valid = pos < n_rows
    s = jnp.where(valid, s, NEG_INF)
    ids = jnp.where(valid, pos.astype(jnp.float32), BIG_ID)
    return _topk_merge(acc_s, acc_i, s, ids, k=k)


def topk_block(acc_s, acc_i, *, k: int):
    """The [REDUCE_ROWS, topk_pad(k)] output block of an accumulator."""
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)
    rows = [jnp.where(lane < k, acc_s, 0.0), jnp.where(lane < k, acc_i, 0.0)]
    pad = jnp.zeros((REDUCE_ROWS - 2, acc_s.shape[1]), jnp.float32)
    return jnp.concatenate(rows + [pad], axis=0)


def _topk_kernel(pt_ref, nrows_ref, pages_ref, *refs, page_rows: int,
                 n_pages: int, k: int, metric: str, quantized: bool):
    if quantized:
        (scales_ref, q_ref, o_ref, buf_ref, sbuf_ref, sem_ref, ssem_ref,
         acc_s_ref, acc_i_ref) = refs
    else:
        scales_ref = sbuf_ref = ssem_ref = None
        q_ref, o_ref, buf_ref, sem_ref, acc_s_ref, acc_i_ref = refs
    n_rows = nrows_ref[0]
    acc_s_ref[...], acc_i_ref[...] = topk_init(k)
    q = q_ref[...]                                        # [1, n_cols]

    def on_page(pi, block):
        acc_s, acc_i = topk_fold_page(
            block, pi, n_rows, q, acc_s_ref[...], acc_i_ref[...],
            page_rows=page_rows, k=k, metric=metric)
        acc_s_ref[...] = acc_s
        acc_i_ref[...] = acc_i

    _page_loop(pt_ref, nrows_ref, pages_ref, scales_ref, buf_ref, sbuf_ref,
               sem_ref, ssem_ref, on_page, page_rows=page_rows,
               n_pages=n_pages)
    o_ref[...] = topk_block(acc_s_ref[...], acc_i_ref[...], k=k)


def topk_scan(pages, page_table, n_rows, query, *, k: int,
              metric: str = "dot", scales=None, interpret: bool = False):
    """Query-scored top-k over an extent's flash-resident pages.

    Same operand contract as :func:`scan_filter_reduce` for ``pages`` /
    ``page_table`` / ``n_rows`` / ``scales``; ``query`` is [1, n_cols]
    f32 (zero-pad to the store width).  ``k`` and ``metric`` (see
    TOPK_METRICS) are static.  Returns [REDUCE_ROWS, topk_pad(k)] f32
    with scores in row 0 and f32 row ids in row 1 — only this tiny
    block ever leaves the device/node.
    """
    if metric not in TOPK_METRICS:
        raise ValueError(f"metric must be one of {TOPK_METRICS}, "
                         f"got {metric!r}")
    if not 1 <= k <= MAX_TOPK:
        raise ValueError(f"k must be in [1, {MAX_TOPK}], got {k}")
    n_phys, page_rows, n_cols = pages.shape
    if query.shape != (1, n_cols):
        raise ValueError(f"query must be [1, {n_cols}], "
                         f"got {query.shape}")
    kpad = topk_pad(k)
    in_specs, operands, scratch = _pipeline_operands(pages, scales)
    # top-k scores and row ids (as f32)
    scratch += [pltpu.VMEM((1, kpad), jnp.float32) for _ in range(2)]
    # the query rides as a plain VMEM block, after the HBM page pools
    in_specs.append(pl.BlockSpec((1, n_cols), lambda pi, pt, nr: (0, 0)))
    operands.append(query.astype(jnp.float32))
    kernel = functools.partial(
        _topk_kernel, page_rows=page_rows, n_pages=page_table.shape[0],
        k=k, metric=metric, quantized=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((REDUCE_ROWS, kpad),
                               lambda pi, pt, nr: (0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((REDUCE_ROWS, kpad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="topk_scan" if scales is None else "topk_scan_q",
    )(page_table, n_rows, *operands)
