"""Plain reference of a hybrid Mamba-2 / attention decoder
(granite-4.0-h-micro), in float32, and its int8 control.

Follows the configuration file (the published ``granitemoehybrid``
equations), not the program.  Embeddings are multiplied by
``embedding_multiplier``; each layer is pre-norm (RMSNorm at
``rms_norm_eps``) and adds ``residual_multiplier`` times its mixer's
output, then ``residual_multiplier`` times a SwiGLU MLP's.  The mixer is
either grouped-query attention with no position embedding
(``position_embedding_type`` "nope") and scores scaled by
``attention_multiplier``, or a Mamba-2 layer:

    z, xBC, dt = split(x @ in_proj)
    xBC = silu(causal depthwise conv(xBC, width mamba_d_conv) + bias)
    x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t,   y_t = h_t C_t + D x_t
    out = (rms(y * silu(z)) * gate_scale) @ out_proj

with the recurrence run token by token in a ``lax.scan`` (never the
chunked form the program prefills with).  Logits are divided by
``logits_scaling``.  Every matmul and contraction runs at ``highest``
precision; the weights are the bf16 values from
``bench/weights_hybrid.py`` for the same seed, widened to float32.
Nothing here imports the program.

The model is run one layer at a time over one sequence.  The control
is the same model with int8 weights and activations in every matmul
(fake-quantized, symmetric absmax: weights per output column,
activations per row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.ref_lm import HI, _mm, _rms, _rope
from bench.weights_hybrid import dims

#: query rows per block of the attention softmax (divides the padded
#: length, a multiple of 512)
Q_BLOCK = 512


def _attention(cfg, q8, x, a):
    """Causal GQA of x [T, d] (already normed); a: this layer's weights."""
    m = dims(cfg)
    t, nh, nkv, hd = x.shape[0], m["nh"], m["nkv"], m["hd"]
    q = _mm(x, a["wq"], q8).reshape(t, nh, hd)
    k = _mm(x, a["wk"], q8).reshape(t, nkv, hd)
    v = _mm(x, a["wv"], q8).reshape(t, nkv, hd)
    if cfg["position_embedding_type"] != "nope":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(Q_BLOCK, t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * \
            cfg["attention_multiplier"]
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(t)[None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, nh * hd)
    return _mm(o, a["wo"], q8)


def _mamba(cfg, q8, x, p):
    """The Mamba-2 mixer over x [T, d] (already normed), from the zero
    state."""
    m = dims(cfg)
    t, di, ds, h, dh, k = x.shape[0], m["di"], m["ds"], m["h"], m["dh"], \
        m["k"]
    zxbcdt = _mm(x, p["in_proj"], q8)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + m["conv"]], axis=-1)
    w = p["conv_w"].astype(jnp.float32)                     # [k, C]
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(pad[i:i + t] * w[i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs, bm, cm = jnp.split(xbc, [di, di + ds], axis=-1)
    xs = xs.reshape(t, h, dh)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))   # [T, H]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    d = p["d_skip"].astype(jnp.float32)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state +
                 (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hds,s->hd", state, c_t, precision=HI) + \
            d[:, None] * x_t
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((h, dh, ds), jnp.float32),
                        (xs, dt, bm, cm))
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = _rms(y, p["gate_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(y, p["out_proj"], q8)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list))))


@functools.lru_cache(maxsize=8)
def _fns(cfg_key: tuple):
    cfg = dict(cfg_key)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]

    def at(stack, i):
        return jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
            w, i, keepdims=False), stack)

    def mlp(h, lw, q8):
        x = _rms(h, lw["mlp_norm"]["scale"], eps)
        m = lw["mlp"]
        g = _mm(x, m["w_gate"], q8)
        return h + r * _mm(jax.nn.silu(g) * _mm(x, m["w_up"], q8),
                           m["w_down"], q8)

    @functools.partial(jax.jit, static_argnames=("q8",))
    def attn_layer(h, layers, i, q8):
        lw = at(layers, i)
        h = h + r * _attention(cfg, q8, _rms(h, lw["attn_norm"]["scale"],
                                             eps), lw["attn"])
        return mlp(h, lw, q8)

    @functools.partial(jax.jit, static_argnames=("q8",))
    def mamba_layer(h, layers, i, q8):
        lw = at(layers, i)
        h = h + r * _mamba(cfg, q8, _rms(h, lw["norm"]["scale"], eps),
                           lw["mamba"])
        return mlp(h, lw, q8)

    @functools.partial(jax.jit, static_argnames=("q8",))
    def head(h, params, q8):
        x = _rms(h, params["final_norm"]["scale"], eps)
        w = (params["embed"]["table"].T if cfg["tie_word_embeddings"]
             else params["lm_head"]["w"])
        return _mm(x, w, q8) / cfg["logits_scaling"]

    return attn_layer, mamba_layer, head


def _bucket(n: int, step: int = 512) -> int:
    return step * -(-n // step)


def forward_logits(cfg: dict, params: dict, tokens: np.ndarray,
                   q8: bool = False, bucket: int = 512):
    """Logits [T_pad, V] f32 of ``tokens`` (padded at the end to a
    multiple of ``bucket``, so a few programs serve every length; both
    mixers are causal, so the padding leaves earlier positions as they
    are)."""
    attn_layer, mamba_layer, head = _fns(_cfg_key(cfg))
    t = len(tokens)
    ids = np.zeros(_bucket(t, bucket), np.int32)
    ids[:t] = tokens
    h = params["embed"]["table"][jnp.asarray(ids)].astype(jnp.float32)
    h = h * cfg["embedding_multiplier"]
    ai = mi = 0
    for kind in cfg["layer_types"]:
        if kind == "attention":
            h = attn_layer(h, params["attn_layers"], jnp.int32(ai), q8=q8)
            ai += 1
        else:
            h = mamba_layer(h, params["mamba_layers"], jnp.int32(mi), q8=q8)
            mi += 1
    return head(h, params, q8=q8)


def logits(cfg: dict, params: dict, prompt: np.ndarray, served: np.ndarray,
           q8: bool = False):
    """Logits of ``prompt`` followed by the served tokens but the last:
    row ``len(prompt) - 1 + i`` is the position whose argmax
    ``served[i]`` should be."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    return forward_logits(cfg, params, seq, q8=q8)


@jax.jit
def _gaps(ref, choice):
    """Per row: best logit - logit of the chosen token (on the device:
    the logits of a long sequence are gigabytes)."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, choice[:, None], -1)[:, 0]


def gaps(ref, prompt_len: int, choices: np.ndarray) -> np.ndarray:
    """How far below the best logit of ``ref`` each of ``choices`` lies,
    one per served position."""
    n = len(choices)
    full = np.zeros(ref.shape[0], np.int32)
    full[prompt_len - 1:prompt_len - 1 + n] = choices
    return np.asarray(_gaps(ref, jnp.asarray(full)))[
        prompt_len - 1:prompt_len - 1 + n]


@jax.jit
def _lead(ref):
    top = jax.lax.top_k(ref, 2)[0]
    return top[:, 0] - top[:, 1]


def margins(cfg: dict, params: dict, prompt: np.ndarray,
            served: np.ndarray) -> np.ndarray:
    """How far the reference's best logit lies above its second, one per
    served position: the room a served token has before it turns."""
    p, n = len(prompt), len(served)
    return np.asarray(_lead(logits(cfg, params, prompt, served)))[
        p - 1:p - 1 + n]


def served_gaps(cfg: dict, params: dict, prompt: np.ndarray,
                served: np.ndarray) -> np.ndarray:
    """Gaps of ``served`` below the reference's best logit, one per
    token."""
    return gaps(logits(cfg, params, prompt, served), len(prompt), served)


def control_gaps(cfg: dict, params: dict, prompt: np.ndarray,
                 served: np.ndarray) -> np.ndarray:
    """Gaps, under the float32 reference, of the tokens the int8 control
    puts first at the served positions."""
    p, n = len(prompt), len(served)
    ctrl = np.asarray(jnp.argmax(logits(cfg, params, prompt, served,
                                        q8=True), axis=-1))[p - 1:p - 1 + n]
    return gaps(logits(cfg, params, prompt, served), p, ctrl)
