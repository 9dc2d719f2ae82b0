"""Hybrid Mamba-2 / attention decoder with per-layer kinds (Granite-4.0-H).

``cfg.layer_types[i]`` names layer ``i``'s mixer: ``"attention"`` (GQA,
RoPE or none, scores scaled by ``attention_multiplier`` when set) or
``"mamba"`` (a Mamba-2 mixer, ``models/mamba2.py``).  Every layer is
pre-norm with a SwiGLU MLP:

    h = embed(tokens) * embedding_multiplier
    h = h + residual_multiplier * mixer(rms(h))
    h = h + residual_multiplier * mlp(rms(h))
    logits = unembed(rms(h)) / logits_scaling

Parameters are stacked per kind, in layer order within each:
``attn_layers`` [n_attention, ...] (``attn_norm``, ``attn``,
``mlp_norm``, ``mlp``) and ``mamba_layers`` [n_mamba, ...] (``norm``,
``mamba``, ``mlp_norm``, ``mlp``).  ``runtime.serve.PagedServer``
serves this layout: attention layers on KV pages, Mamba layers on
per-sequence state slots; ``bench/ref_hybrid.py`` is the plain forward
pass it is checked against.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.mamba2 import D_CONV, init_mamba2


def layer_runs(layer_types):
    """The layer pattern as ``(n_periods, runs)``: the shortest period
    that repeats to the whole stack, split into runs of one kind,
    ``[(kind, count), ...]``."""
    n = len(layer_types)
    for p in range(1, n + 1):
        if n % p == 0 and tuple(layer_types[:p]) * (n // p) == \
                tuple(layer_types):
            break
    runs = []
    for t in layer_types[:p]:
        if runs and runs[-1][0] == t:
            runs[-1] = (t, runs[-1][1] + 1)
        else:
            runs.append((t, 1))
    return n // p, runs


class HybridLM:
    def __init__(self, cfg, compute_dtype=jnp.bfloat16):
        if cfg.ssm_groups != 1 or cfg.ssm_conv != D_CONV:
            raise ValueError("the Mamba-2 mixer takes 1 group and a conv of "
                             f"{D_CONV}, got {cfg.ssm_groups} and "
                             f"{cfg.ssm_conv}")
        if set(cfg.layer_types) - {"attention", "mamba"}:
            raise ValueError(f"unknown layer kinds in {cfg.layer_types}")
        self.cfg = cfg
        self.compute_dtype = compute_dtype

    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        cfg = self.cfg
        k_embed, k_head, k_attn, k_mamba = jax.random.split(rng, 4)

        def attn_layer(key):
            ks = jax.random.split(key, 2)
            return {"attn_norm": L.init_norm(None, cfg.d_model, "rmsnorm",
                                             dtype),
                    "attn": L.init_attention(ks[0], cfg, dtype),
                    "mlp_norm": L.init_norm(None, cfg.d_model, "rmsnorm",
                                            dtype),
                    "mlp": L.init_mlp(ks[1], cfg, dtype)}

        def mamba_layer(key):
            ks = jax.random.split(key, 2)
            return {"norm": L.init_norm(None, cfg.d_model, "rmsnorm", dtype),
                    "mamba": init_mamba2(ks[0], cfg, dtype),
                    "mlp_norm": L.init_norm(None, cfg.d_model, "rmsnorm",
                                            dtype),
                    "mlp": L.init_mlp(ks[1], cfg, dtype)}

        params = {
            "embed": L.init_embed(k_embed, cfg, dtype),
            "final_norm": L.init_norm(None, cfg.d_model, "rmsnorm", dtype),
            "attn_layers": jax.vmap(attn_layer)(
                jax.random.split(k_attn, cfg.attn_layers)),
            "mamba_layers": jax.vmap(mamba_layer)(
                jax.random.split(k_mamba, cfg.state_layers)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": L.dense_init(
                k_head, (cfg.d_model, cfg.vocab_size), dtype=dtype)}
        return params
