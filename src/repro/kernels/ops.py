"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend (tests, ``JAX_PLATFORMS=cpu`` rehearsals) the kernels
execute under ``interpret=True``; on TPU they compile natively; any
other backend raises (see :func:`interpret_mode`).  ``ref.py`` holds
the pure-jnp oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref  # noqa: F401  (re-exported oracle module)
from repro.kernels.embed_agg import (embed_agg as _embed_agg,
                                     embed_gather as _embed_gather,
                                     validate_embed_args)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.isp_scan import (scan_filter_reduce as _scan_reduce,
                                    topk_scan as _topk_scan,
                                    REDUCE_ROWS, topk_pad)  # noqa: F401
from repro.kernels.paged_attention import (paged_attention as _paged,
                                            paged_attention_q8 as _paged_q8)
from repro.kernels.rwkv_scan import rwkv_scan as _rwkv


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: on the ``cpu`` backend
    yes, on ``tpu`` no.  Any other backend has no lowering for these
    TPU kernels, and silently interpreting them there would hide the
    device, so it raises."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels need the 'tpu' backend "
                           f"(or 'cpu' to interpret them), got {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, lengths,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    return _paged(q, k_pages, v_pages, page_table, lengths,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_q8(q, k_pages, v_pages, k_scale, v_scale, page_table,
                       lengths, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    return _paged_q8(q, k_pages, v_pages, k_scale, v_scale, page_table,
                     lengths, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _embed_agg_jit(table, indices, weights, interpret: bool):
    return _embed_agg(table, indices, weights, interpret=interpret)


def embed_agg(table, indices, weights=None, interpret: bool | None = None):
    """Validating wrapper: dtype/bounds are checked *eagerly* (out-of-range
    vocab ids raise instead of silently clamping), then the jitted kernel
    runs."""
    if interpret is None:
        interpret = interpret_mode()
    indices = jnp.asarray(indices)
    validate_embed_args(table, indices)
    return _embed_agg_jit(table, indices, weights, interpret)


def _pow2_pad_table(page_table):
    """Pad a page table to the next pow2 length (with page id 0) so the
    scan kernel compiles one program per size bucket; padded iterations
    fall past ``n_rows`` and are skipped by the kernel."""
    pps = page_table.shape[0]
    target = 1 << max(pps - 1, 0).bit_length()
    if target == pps:
        return page_table
    return jnp.pad(page_table, (0, target - pps))


@functools.partial(jax.jit, static_argnames=("filter_col", "filter_op",
                                             "interpret"))
def _scan_reduce_jit(pages, page_table, n_rows, threshold, scales,
                     filter_col, filter_op, interpret):
    # the double-buffered kernel DMAs exactly the extent's valid pages
    # out of the (HBM-resident) pool — no interpret-mode compaction
    # gather is needed anymore, and padded table entries cost nothing
    return _scan_reduce(pages, page_table, n_rows, threshold,
                        scales=scales, filter_col=filter_col,
                        filter_op=filter_op, interpret=interpret)


def scan_filter_reduce(pages, page_table, n_rows, threshold=0.0, *,
                       scales=None, filter_col: int = 0,
                       filter_op: str = "all",
                       interpret: bool | None = None):
    """In-storage filtered aggregate over extent pages (jitted,
    double-buffered page pipeline, with the page table padded to a pow2
    bucket to bound recompiles).

    pages: [n_phys, page_rows, n_cols]; page_table: [pps] int32;
    n_rows/threshold: python scalars or [1] arrays; scales: optional
    [n_phys, page_rows] f32 per-row scales of a quantized pool (the
    kernel dequantizes in VMEM — see ``kernels.isp_scan``).
    Returns [8, n_cols] f32 — see ``kernels.isp_scan`` for the layout."""
    if interpret is None:
        interpret = interpret_mode()
    pt = _pow2_pad_table(jnp.asarray(page_table, jnp.int32).reshape(-1))
    nr = jnp.asarray(n_rows, jnp.int32).reshape(1)
    th = jnp.asarray(threshold, jnp.float32).reshape(1)
    return _scan_reduce_jit(pages, pt, nr, th, scales, filter_col,
                            filter_op, interpret)


@functools.partial(jax.jit, static_argnames=("page_rows", "filter_col",
                                             "filter_op"))
def scan_filter_reduce_host(data, threshold=0.0, *, page_rows: int,
                            filter_col: int = 0, filter_op: str = "all"):
    """The host-side reference path (host reads everything, then folds
    page-sequentially) — bit-identical to the in-storage kernel."""
    return ref.scan_filter_reduce_ref(data, page_rows, threshold,
                                      filter_col=filter_col,
                                      filter_op=filter_op)


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def _topk_jit(pages, page_table, n_rows, query, scales, k, metric,
              interpret):
    return _topk_scan(pages, page_table, n_rows, query, k=k, metric=metric,
                      scales=scales, interpret=interpret)


def topk_scan(pages, page_table, n_rows, query, *, k: int,
              metric: str = "dot", scales=None,
              interpret: bool | None = None):
    """In-storage query-scored top-k over extent pages (jitted, same
    double-buffered page pipeline and pow2 table bucketing as
    ``scan_filter_reduce``).

    query: [n_cols] (or [1, n_cols]) — zero-pad to the store width.
    Returns [8, topk_pad(k)] f32: scores row 0 (descending), f32 row
    ids row 1; empty slots hold (-1e30, 2^30).  Only this block ever
    crosses the wire — the retrieval wire-reduction story."""
    if interpret is None:
        interpret = interpret_mode()
    n_cols = pages.shape[2]
    pt = _pow2_pad_table(jnp.asarray(page_table, jnp.int32).reshape(-1))
    nr = jnp.asarray(n_rows, jnp.int32).reshape(1)
    q = jnp.asarray(query, jnp.float32).reshape(1, n_cols)
    return _topk_jit(pages, pt, nr, q, scales, k, metric, interpret)


@functools.partial(jax.jit, static_argnames=("page_rows", "k", "metric"))
def topk_scan_host(data, query, *, page_rows: int, k: int,
                   metric: str = "dot"):
    """The host-side retrieval baseline (host fetches the whole extent,
    then folds page-sequentially) — bit-identical to ``topk_scan``."""
    return ref.topk_scan_ref(data, query, page_rows=page_rows, k=k,
                             metric=metric)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _embed_gather_jit(table, indices, interpret: bool):
    return _embed_gather(table, indices, interpret=interpret)


def embed_gather(table, indices, interpret: bool | None = None):
    """Validating wrapper over the batched [B, K] row gather (eager
    bounds check like ``embed_agg``, then one jit per shape bucket)."""
    if interpret is None:
        interpret = interpret_mode()
    indices = jnp.asarray(indices)
    validate_embed_args(table, indices)
    return _embed_gather_jit(table, indices, interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r, k, v, logw, u, s0, chunk: int = 32,
              interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    return _rwkv(r, k, v, logw, u, s0, chunk=chunk, interpret=interpret)
