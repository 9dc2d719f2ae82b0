"""Uniform Model interface over the architecture zoo.

``get_model(cfg)`` returns a ``Model`` exposing:
  init / loss / forward / prefill / decode_step / cache_spec / init_cache /
  input_specs(shape) — the ShapeDtypeStruct stand-ins used by the multi-pod
  dry-run (weak-type-correct, shardable, no device allocation).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import frontends
from repro.models.hybrid import HybridLM
from repro.models.mamba2 import Zamba2LM
from repro.models.rwkv6 import RWKV6LM
from repro.models.transformer import TransformerLM


class Model:
    """Thin uniform facade; ``impl`` is the family-specific module."""

    def __init__(self, cfg: ArchConfig, impl):
        self.cfg = cfg
        self.impl = impl

    # delegate the functional API
    def init(self, rng, dtype=jnp.float32):
        return self.impl.init(rng, dtype)

    def loss(self, params, batch):
        return self.impl.loss(params, batch)

    def forward(self, params, batch):
        return self.impl.forward(params, batch)

    def prefill(self, params, batch, cache_dtype=jnp.bfloat16):
        return self.impl.prefill(params, batch, cache_dtype)

    def decode_step(self, params, cache, tokens):
        return self.impl.decode_step(params, cache, tokens)

    def cache_spec(self, batch, seq, dtype=jnp.bfloat16):
        return self.impl.cache_spec(batch, seq, dtype)

    def init_cache(self, batch, seq, dtype=jnp.bfloat16):
        return self.impl.init_cache(batch, seq, dtype)

    # ------------------------------------------------------------------
    def uses_embeds(self) -> bool:
        """Frontend archs feed precomputed embeddings for train/prefill."""
        return self.cfg.frontend in ("vision", "audio")

    def input_specs(self, shape: ShapeConfig,
                    embed_dtype=jnp.bfloat16) -> Dict[str, Any]:
        """Dry-run input ShapeDtypeStructs for one assigned shape."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if shape.kind == "train":
            if self.uses_embeds():
                return {"embeds": frontends.frontend_embed_spec(cfg, b, s,
                                                                embed_dtype),
                        "labels": tok}
            return {"tokens": tok, "labels": tok}
        if shape.kind == "prefill":
            if self.uses_embeds():
                return {"embeds": frontends.frontend_embed_spec(cfg, b, s,
                                                                embed_dtype)}
            return {"tokens": tok}
        # decode: one new token against a seq_len cache
        return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32),
                "cache": self.cache_spec(b, s)}

    def synth_batch(self, shape: ShapeConfig, rng=None,
                    dtype=jnp.float32) -> Dict[str, Any]:
        """Concrete synthetic batch matching input_specs (smoke tests)."""
        cfg = self.cfg
        if rng is None:
            rng = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(rng)
        b, s = shape.global_batch, shape.seq_len
        toks = jax.random.randint(k1, (b, s), 0, cfg.vocab_size, jnp.int32)
        if shape.kind == "train":
            if self.uses_embeds():
                return {"embeds": frontends.synth_embeddings(cfg, b, s, k2,
                                                             dtype),
                        "labels": toks}
            return {"tokens": toks, "labels": toks}
        if shape.kind == "prefill":
            if self.uses_embeds():
                return {"embeds": frontends.synth_embeddings(cfg, b, s, k2,
                                                             dtype)}
            return {"tokens": toks}
        return {"tokens": toks[:, 0], "cache": self.init_cache(b, s)}

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def active_param_count(self, params) -> int:
        """MoE-aware: only top_k/n_experts of expert weights are active."""
        cfg = self.cfg
        if not cfg.is_moe:
            return self.param_count(params)
        total = 0
        flat = jax.tree.flatten_with_path(params)[0] if hasattr(jax.tree, "flatten_with_path") else None
        # expert tensors have leading dim n_experts inside "mlp"
        def visit(path, leaf):
            nonlocal total
            keys = [getattr(p, "key", str(p)) for p in path]
            if "mlp" in keys and leaf.ndim >= 3 and leaf.shape[-3] == cfg.n_experts:
                total += int(leaf.size * cfg.top_k / cfg.n_experts)
            elif "mlp" in keys and leaf.ndim >= 4 and leaf.shape[1] == cfg.n_experts:
                total += int(leaf.size * cfg.top_k / cfg.n_experts)
            else:
                total += leaf.size
        jax.tree_util.tree_map_with_path(visit, params)
        return total


def _filter_kwargs(cls, kw):
    import inspect
    sig = inspect.signature(cls.__init__)
    return {k: v for k, v in kw.items() if k in sig.parameters}


def get_model(cfg: ArchConfig, compute_dtype=jnp.bfloat16, **kw) -> Model:
    cls = {"rwkv6": RWKV6LM, "mamba2_hybrid": Zamba2LM,
           "hybrid": HybridLM}.get(
        cfg.block_type, TransformerLM)
    impl = cls(cfg, compute_dtype=compute_dtype, **_filter_kwargs(cls, kw))
    return Model(cfg, impl)
