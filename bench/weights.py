"""Seeded random weights of a decoder LM, in the layout the server takes.

One jitted call makes every leaf on the device, in the dtype it is
served in.  The same ``(config, seed)`` always gives the same values,
so the plain reference (``bench/ref_lm.py``) makes its own copy from
the seed and takes nothing from the program.

Layout (stacked per-layer leaves, as ``repro.models.transformer``
consumes them): ``embed.table [V, d]``, ``final_norm.scale [d]``,
``layers.{attn_norm,mlp_norm}.scale [L, d]``,
``layers.attn.{wq [L, d, H*hd], wk/wv [L, d, Hkv*hd], wo [L, H*hd, d]}``,
``layers.mlp.{w_gate,w_up [L, d, f], w_down [L, f, d]}`` and, for an
untied head, ``lm_head.w [d, V]``.

Matrices are N(0, 1/fan_in); the embedding is N(0, 0.02^2) as in the
program's own init; norm scales are 1 + N(0, 0.05^2), so a path that
dropped a norm scale would not pass the reference check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    """{path: (shape, std, mean)} of every leaf of ``cfg``."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "embed/table": ((v, d), 0.02, 0.0),
        "final_norm/scale": ((d,), 0.05, 1.0),
        "layers/attn_norm/scale": ((L, d), 0.05, 1.0),
        "layers/mlp_norm/scale": ((L, d), 0.05, 1.0),
        "layers/attn/wq": ((L, d, h * hd), d ** -0.5, 0.0),
        "layers/attn/wk": ((L, d, hkv * hd), d ** -0.5, 0.0),
        "layers/attn/wv": ((L, d, hkv * hd), d ** -0.5, 0.0),
        "layers/attn/wo": ((L, h * hd, d), (h * hd) ** -0.5, 0.0),
        "layers/mlp/w_gate": ((L, d, f), d ** -0.5, 0.0),
        "layers/mlp/w_up": ((L, d, f), d ** -0.5, 0.0),
        "layers/mlp/w_down": ((L, f, d), f ** -0.5, 0.0),
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head/w"] = ((d, v), d ** -0.5, 0.0)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight of ``cfg`` from ``seed`` (a 31-bit int), on the
    default device, in ``dtype``, made by one jitted call."""
    spec = shapes(cfg)
    return _make(tuple(sorted((p, s, sd, m) for p, (s, sd, m) in
                              spec.items())), jnp.dtype(dtype))(
        jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=4)
def _make(spec: tuple, dtype):
    def fn(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (path, shape, std, mean) in zip(keys, spec):
            x = jax.random.normal(k, shape, jnp.float32) * std + mean
            flat[path] = x.astype(dtype)
        return _nest(flat)
    return jax.jit(fn)
