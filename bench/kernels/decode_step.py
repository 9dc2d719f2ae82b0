"""Model operations of decode steps of a dense decoder LM.

Counts what the model needs per generated token: every weight matmul
(2 operations per multiply-add), the unembedding, and attention over the
token's context.  Norms, RoPE and softmax are left out (a few per cent
at most).  Padding rows of a pow2-bucketed batch do no useful work and
are not counted.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per_layer = d * (h + 2 * hkv) * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def token_ops(cfg: dict, context: int) -> int:
    """Operations to decode one token that attends over ``context``."""
    attn = (4 * context * cfg["num_attention_heads"] * cfg["head_dim"] *
            cfg["num_hidden_layers"])
    return 2 * matmul_params(cfg) + attn


def horizon_ops(cfg: dict, rows, horizon: int) -> int:
    """Operations of one decode horizon; ``rows`` as in
    ``paged_attention.horizon_cost``."""
    return sum(token_ops(cfg, n + i + 1)
               for i in range(horizon) for n, e in rows if e > i)
