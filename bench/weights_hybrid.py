"""Seeded random weights of a hybrid Mamba-2 / attention decoder
(granite-4.0-h-micro), in the layout the server takes.

One jitted call makes every leaf on the device, in the dtype it is
served in; the same ``(config, seed)`` always gives the same values, so
the plain reference (``bench/ref_hybrid.py``) makes its own copy.

Layout (``repro.models.hybrid``): ``embed.table [V, d]``,
``final_norm.scale [d]``; per attention layer, stacked over the
``La`` of them, ``attn_layers.{attn_norm,mlp_norm}.scale``,
``attn_layers.attn.{wq, wk, wv, wo}``, ``attn_layers.mlp.{w_gate, w_up,
w_down}``; per Mamba layer, stacked over the ``Lm`` of them,
``mamba_layers.{norm,mlp_norm}.scale``, ``mamba_layers.mlp.*`` and
``mamba_layers.mamba.{in_proj [d, 2*di + 2*G*ds + H], conv_w [4, C],
conv_b [C], a_log [H], dt_bias [H], d_skip [H], gate_norm.scale [di],
out_proj [di, d]}`` with ``di = expand * d`` and ``C = di + 2*G*ds``.

Matrices are N(0, 1/fan_in), norm scales and ``D`` 1 + N(0, 0.05^2).
The embedding is N(0, (0.02 / embedding_multiplier)^2), so the scaled
embedding enters the residual stream at 0.02 rms: at 0.02 itself (0.24
after the multiplier) the embedding outweighs what the layers add
(``residual_multiplier`` times each branch), and with tied embeddings
every position's best logit is its own input token by a wide margin,
so greedy decoding repeats the prompt's last token.

As Mamba-2 initialises them, ``A = -exp(a_log)`` with ``exp(a_log)``
uniform in [1, 16], and ``dt_bias`` is the inverse softplus of a ``dt``
log-uniform in [0.001, 0.1]; the conv weights are N(0, 1/4) (fan-in 4)
and its bias N(0, 0.1^2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import _nest


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    g, ds, h = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                cfg["mamba_n_heads"])
    types = cfg["layer_types"]
    return {"d": d, "di": di, "ds": ds, "g": g, "h": h,
            "dh": cfg["mamba_d_head"], "conv": di + 2 * g * ds,
            "k": cfg["mamba_d_conv"],
            "la": sum(t == "attention" for t in types),
            "lm": sum(t == "mamba" for t in types),
            "nh": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"]}


def shapes(cfg: dict) -> dict:
    """{path: (shape, kind, a, b)} of every leaf: ``normal`` with std
    ``a`` and mean ``b``, ``a_log`` or ``dt_bias``."""
    m = dims(cfg)
    d, f = m["d"], m["f"]
    emb = 0.02 / cfg["embedding_multiplier"]
    out = {"embed/table": ((m["v"], d), "normal", emb, 0.0),
           "final_norm/scale": ((d,), "normal", 0.05, 1.0)}
    for stack, n in (("attn_layers", m["la"]), ("mamba_layers", m["lm"])):
        norms = ("attn_norm", "mlp_norm") if stack == "attn_layers" \
            else ("norm", "mlp_norm")
        for nm in norms:
            out[f"{stack}/{nm}/scale"] = ((n, d), "normal", 0.05, 1.0)
        out[f"{stack}/mlp/w_gate"] = ((n, d, f), "normal", d ** -0.5, 0.0)
        out[f"{stack}/mlp/w_up"] = ((n, d, f), "normal", d ** -0.5, 0.0)
        out[f"{stack}/mlp/w_down"] = ((n, f, d), "normal", f ** -0.5, 0.0)
    la, lm = m["la"], m["lm"]
    q, kv = m["nh"] * m["hd"], m["nkv"] * m["hd"]
    out.update({
        "attn_layers/attn/wq": ((la, d, q), "normal", d ** -0.5, 0.0),
        "attn_layers/attn/wk": ((la, d, kv), "normal", d ** -0.5, 0.0),
        "attn_layers/attn/wv": ((la, d, kv), "normal", d ** -0.5, 0.0),
        "attn_layers/attn/wo": ((la, q, d), "normal", q ** -0.5, 0.0),
    })
    h, di, conv = m["h"], m["di"], m["conv"]
    mb = "mamba_layers/mamba/"
    out.update({
        mb + "in_proj": ((lm, d, di + conv + h), "normal", d ** -0.5, 0.0),
        mb + "conv_w": ((lm, m["k"], conv), "normal", m["k"] ** -0.5, 0.0),
        mb + "conv_b": ((lm, conv), "normal", 0.1, 0.0),
        mb + "a_log": ((lm, h), "a_log", 0.0, 0.0),
        mb + "dt_bias": ((lm, h), "dt_bias", 0.0, 0.0),
        mb + "d_skip": ((lm, h), "normal", 0.05, 1.0),
        mb + "gate_norm/scale": ((lm, di), "normal", 0.05, 1.0),
        mb + "out_proj": ((lm, di, d), "normal", di ** -0.5, 0.0),
    })
    return out


def make(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight of ``cfg`` from ``seed`` (a 31-bit int), on the
    default device, in ``dtype``, made by one jitted call."""
    spec = shapes(cfg)
    return _make(tuple(sorted((p,) + v for p, v in spec.items())),
                 jnp.dtype(dtype))(jax.random.PRNGKey(seed))


def _leaf(key, shape, kind, a, b):
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        lo, hi = jnp.log(0.001), jnp.log(0.1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    return jax.random.normal(key, shape, jnp.float32) * a + b


@functools.lru_cache(maxsize=4)
def _make(spec: tuple, dtype):
    def fn(key):
        keys = jax.random.split(key, len(spec))
        return _nest({path: _leaf(k, shape, kind, a, b).astype(dtype)
                      for k, (path, shape, kind, a, b) in zip(keys, spec)})
    return jax.jit(fn)
