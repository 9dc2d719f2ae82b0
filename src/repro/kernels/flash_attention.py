"""Blockwise causal flash attention (prefill) — Pallas TPU kernel.

Grid: (batch, q_heads, num_q_blocks, num_k_blocks); the k-block axis is
the innermost (sequential) dimension so the online-softmax accumulators
live in VMEM scratch across k steps.  GQA is handled in the k/v index
maps (kv_head = q_head // group).  Block shapes are MXU-aligned
(multiples of 128 on the lane dim, block_q x head_dim tiles in VMEM).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, block_q: int, block_k: int, sm_scale: float,
                  num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        # skip k blocks strictly above the diagonal
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0, :, :].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                  # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q: [B, H, Sq, D]; k/v: [B, Hkv, Sk, D] -> [B, H, Sq, D]."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    sm_scale = 1.0 / math.sqrt(d)

    grid = (b, h, nq, nk)
    kernel = functools.partial(_flash_kernel, causal=causal, block_q=block_q,
                               block_k=block_k, sm_scale=sm_scale,
                               num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, hh, qi, ki: (bb, hh // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, hh, qi, ki: (bb, hh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
