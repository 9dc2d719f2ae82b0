"""Embedding-bag aggregation — Pallas TPU kernel (DLRM 'embed' workload).

The paper's rm1/rm2 workloads do sparse embedding-table lookups +
sum-pooling inside the SSD.  TPU adaptation: the table shard lives in
HBM ("flash"); lookup indices arrive via scalar prefetch so each grid
step DMAs exactly one table row HBM->VMEM and accumulates the pool in
the output block — a near-data gather that never materializes [B, L, D].

Grid: (batch, lookups); the lookup axis is sequential per sample.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _embed_kernel(idx_ref, w_ref, table_ref, o_ref, *, weighted: bool):
    b = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    row = table_ref[0, :].astype(jnp.float32)
    if weighted:
        row = row * w_ref[b, li]
    o_ref[0, :] = o_ref[0, :] + row.astype(o_ref.dtype)


def validate_embed_args(table, indices):
    """Reject malformed lookups before they reach ``pallas_call``.

    The BlockSpec index map would silently clamp an out-of-range vocab
    id to the last table row — a wrong answer, not an error — so bounds
    are checked here whenever the indices are concrete (eager callers;
    tracers inside an enclosing jit skip the value check but still get
    the dtype/shape checks).
    """
    if indices.ndim != 2:
        raise ValueError(f"indices must be [B, L], got shape "
                         f"{tuple(indices.shape)}")
    if not jnp.issubdtype(indices.dtype, jnp.integer):
        raise TypeError(f"indices must be an integer dtype (int32), got "
                        f"{indices.dtype}")
    v = table.shape[0]
    if not isinstance(indices, jax.core.Tracer):
        # one fused reduction -> one host transfer (not two syncs)
        lo, hi = map(int, jax.device_get(
            jnp.stack([jnp.min(indices), jnp.max(indices)])))
        if lo < 0 or hi >= v:
            raise ValueError(
                f"embedding indices out of range: min={lo} max={hi} but "
                f"vocab size is {v} (valid ids are [0, {v - 1}])")


def embed_agg(table, indices, weights=None, *, interpret: bool = False):
    """table: [V, D]; indices: [B, L] int32; weights: optional [B, L] f32.
    Returns [B, D] sum-pooled embeddings."""
    validate_embed_args(table, indices)
    v, d = table.shape
    b, l = indices.shape
    weighted = weights is not None
    if weights is None:
        weights = jnp.ones((b, l), jnp.float32)

    kernel = functools.partial(_embed_kernel, weighted=weighted)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, l),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # weights, whole array
            pl.BlockSpec((1, d), lambda bb, li, idx: (idx[bb, li], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda bb, li, idx: (bb, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="embed_agg",
    )(indices, weights, table)


def _gather_kernel(idx_ref, table_ref, o_ref):
    o_ref[0, :] = table_ref[0, :]


def embed_gather(table, indices, *, interpret: bool = False):
    """Batched multi-query row gather (the retrieval-assembly side).

    table: [V, D]; indices: [B, K] int32 — e.g. one top-k id table per
    query.  Returns [B, K, D] = table[indices], gathered in ONE kernel
    launch (one jit per [B, K] bucket) so retrieval assembly never
    loops host-side per request.  Rows keep the table's dtype (token
    blocks stay int32).
    """
    validate_embed_args(table, indices)
    v, d = table.shape
    b, kk = indices.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kk),
        in_specs=[
            pl.BlockSpec((1, d), lambda bb, li, idx: (idx[bb, li], 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda bb, li, idx: (bb * kk + li, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kk, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="embed_gather",
    )(indices, table)
    return out.reshape(b, kk, d)
