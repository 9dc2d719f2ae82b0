"""Model operations of decode steps of a hybrid Mamba-2 / attention
decoder (granite-4.0-h-micro).

Counts what the model needs per generated token: every weight matmul (2
operations per multiply-add) — the attention layers' projections, the
Mamba layers' ``in_proj`` and ``out_proj``, every layer's SwiGLU MLP —
and the unembedding; attention over the token's context in the
attention layers; the Mamba state update and output (5 operations per
state element, ``bench/kernels/ssm_state.py``) and the depthwise conv
(2 per tap and channel).  Norms, gates and softplus are left out.
Padding rows of a pow2-bucketed batch do no useful work and are not
counted.
"""
from __future__ import annotations

import os

from bench import harness

ssm_state = harness.load_module(os.path.join(harness.BENCH, "kernels",
                                             "ssm_state.py"))


def _layers(cfg: dict):
    t = cfg["layer_types"]
    return sum(k == "attention" for k in t), sum(k == "mamba" for k in t)


def matmul_params(cfg: dict) -> int:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    di = cfg["mamba_expand"] * d
    g, ds, h = cfg["mamba_n_groups"], cfg["mamba_d_state"], \
        cfg["mamba_n_heads"]
    n_attn, n_mamba = _layers(cfg)
    attn = d * (nh + 2 * nkv) * hd + nh * hd * d
    mamba = d * (2 * di + 2 * g * ds + h) + di * d
    return (n_attn * attn + n_mamba * mamba +
            cfg["num_hidden_layers"] * 3 * d * f + d * v)


def token_ops(cfg: dict, context: int) -> int:
    """Operations to decode one token that attends over ``context``."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    n_attn, n_mamba = _layers(cfg)
    conv = cfg["mamba_expand"] * d + 2 * cfg["mamba_n_groups"] * \
        cfg["mamba_d_state"]
    state, _ = ssm_state.row_cost(**ssm_state.dims(cfg))
    attn = 4 * context * nh * (d // nh) * n_attn
    return (2 * matmul_params(cfg) + attn +
            n_mamba * (state + 2 * cfg["mamba_d_conv"] * conv))


def horizon_ops(cfg: dict, rows, horizon: int) -> int:
    """Operations of one decode horizon; ``rows``: per sequence
    (committed length before the horizon, tokens it emitted)."""
    return sum(token_ops(cfg, n + i + 1)
               for i in range(horizon) for n, e in rows if e > i)
