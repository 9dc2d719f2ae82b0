"""RWKV6 chunked wkv recurrence — Pallas TPU kernel.

Grid: (B*H, n_chunks); the chunk axis is sequential so the [dk, dv]
recurrent state persists in VMEM scratch across chunks.  All exponents
are differences of cumulative log-decays (<= 0), f32-safe.  The chunk
math matches ``repro.models.rwkv6.wkv_chunk`` and is validated against
the per-token oracle in ``ref.wkv_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # [C, dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # [C, dv]
    logw = w_ref[0].astype(jnp.float32)       # [C, dk]
    u = u_ref[0].astype(jnp.float32)          # [dk]
    s0 = state_ref[...]

    cum = jnp.cumsum(logw, axis=0)
    cum_excl = cum - logw
    diff = cum_excl[:, None, :] - cum[None, :, :]          # [t, s, dk]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows > cols
    dmat = jnp.exp(jnp.where(tri[:, :, None], diff, -jnp.inf))
    scores = jnp.einsum("ti,si,tsi->ts", r, k, dmat)
    diag = jnp.sum(r * u[None, :] * k, axis=-1)
    o = scores @ v + diag[:, None] * v
    o = o + (r * jnp.exp(cum_excl)) @ s0
    o_ref[0] = o.astype(o_ref.dtype)

    k2 = k * jnp.exp(cum[-1][None, :] - cum)
    state_ref[...] = jnp.exp(cum[-1])[:, None] * s0 + k2.T @ v

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sT_ref[0] = state_ref[...].astype(sT_ref.dtype)


def rwkv_scan(r, k, v, logw, u, s0, *, chunk: int = 32,
              interpret: bool = False):
    """r/k/logw: [B,S,H,dk]; v: [B,S,H,dv]; u: [H,dk]; s0: [B,H,dk,dv].
    Returns (o [B,S,H,dv], sT [B,H,dk,dv])."""
    b, s, h, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    bh = b * h

    def flat(x):  # [B,S,H,*] -> [B*H, S, *]
        return jnp.moveaxis(x, 2, 1).reshape(bh, s, -1)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(logw)
    s0f = s0.reshape(bh, dk, dv)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc)
    o, sT = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, dk), lambda i, c: (i % h, 0)),
            pl.BlockSpec((1, dk, dv), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), r.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv_scan",
    )(rf, kf, vf, wf, u, s0f)
    o = jnp.moveaxis(o.reshape(b, h, s, dv), 1, 2)
    return o, sT.reshape(b, h, dk, dv)
