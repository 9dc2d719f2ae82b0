"""Plain reference of a dense decoder LM, in float32, and its int8 control.

Follows the configuration file, not the program: RMSNorm with the
file's epsilon, rotate-half RoPE at ``rope_theta``, grouped-query
attention (query head ``i`` reads KV head ``i // (H / Hkv)``) with a
causal softmax scaled by ``1/sqrt(head_dim)``, a SwiGLU MLP and a tied
(or separate) unembedding.  Every matmul runs at ``highest`` precision;
the weights are the bf16 values from ``bench/weights.py`` for the same
seed, widened to float32.  Nothing here imports the program.

The model is run one layer at a time over one sequence, so the peak is
one layer's activations and never the program's cache.

``served_gaps`` teacher-forces a prompt with its served tokens and
returns, for each served token, how far its logit lies below the
reference's best at that position.  ``control_gaps`` returns, at the
same positions, the gap of the token that an int8 copy of the reference
puts first: weights fake-quantized per output column and activations
per row, both symmetric absmax on the int8 grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric absmax int8 fake quantization along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s).clip(-127, 127) * s


def _mm(x, w, q8: bool):
    """x [T, a] @ w [a, b] in f32; int8 on both sides when ``q8``."""
    w = w.astype(jnp.float32)
    if q8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [T, heads, hd], positions 0..T-1, rotate-half pairing."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, q8, h, lw):
    """One decoder layer over h [T, d] f32; lw: this layer's weights."""
    t = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    x = _rms(h, lw["attn_norm"]["scale"], eps)
    a = lw["attn"]
    q = _rope(_mm(x, a["wq"], q8).reshape(t, nh, hd), cfg["rope_theta"])
    k = _rope(_mm(x, a["wk"], q8).reshape(t, nkv, hd), cfg["rope_theta"])
    v = _mm(x, a["wv"], q8).reshape(t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, nh * hd)
    h = h + _mm(o, a["wo"], q8)
    x = _rms(h, lw["mlp_norm"]["scale"], eps)
    m = lw["mlp"]
    g = _mm(x, m["w_gate"], q8)
    u = _mm(x, m["w_up"], q8)
    return h + _mm(jax.nn.silu(g) * u, m["w_down"], q8)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.lru_cache(maxsize=8)
def _fns(cfg_key: tuple):
    cfg = dict(cfg_key)

    @functools.partial(jax.jit, static_argnames=("q8",))
    def layer(h, layers, i, q8):
        lw = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
            w, i, keepdims=False), layers)
        return _layer(cfg, q8, h, lw)

    @functools.partial(jax.jit, static_argnames=("q8",))
    def head(h, params, q8):
        x = _rms(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])
        w = (params["embed"]["table"].T if cfg["tie_word_embeddings"]
             else params["lm_head"]["w"])
        return _mm(x, w, q8)

    @jax.jit
    def gaps(ref, choice):
        """Per row: best logit - logit of the chosen token."""
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, choice[:, None], -1)[:, 0]

    return layer, head, gaps


def _bucket(n: int, step: int = 512) -> int:
    return step * -(-n // step)


def forward_logits(cfg: dict, params: dict, tokens: np.ndarray,
                   q8: bool = False):
    """Logits [T_pad, V] f32 of ``tokens`` (padded at the end to a
    multiple of 512 so a few programs serve every length; causal
    attention keeps the padding out of earlier positions)."""
    layer, head, _ = _fns(_cfg_key(cfg))
    t = len(tokens)
    ids = np.zeros(_bucket(t), np.int32)
    ids[:t] = tokens
    h = params["embed"]["table"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        h = layer(h, params["layers"], jnp.int32(i), q8=q8)
    return head(h, params, q8=q8)


def logits(cfg: dict, params: dict, prompt: np.ndarray,
           served: np.ndarray, q8: bool = False):
    """Logits [T_pad, V] (on the device) of ``prompt`` followed by the
    served tokens but the last: row ``len(prompt) - 1 + i`` is the
    position whose argmax ``served[i]`` should be."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    return forward_logits(cfg, params, seq, q8=q8)


def gaps(cfg: dict, ref, prompt_len: int, choices: np.ndarray) -> np.ndarray:
    """How far below the best logit of ``ref`` (from :func:`logits`)
    each of ``choices`` lies, one per served position."""
    fn = _fns(_cfg_key(cfg))[2]
    full = np.zeros(ref.shape[0], np.int32)
    n = len(choices)
    full[prompt_len - 1:prompt_len - 1 + n] = choices
    return np.asarray(fn(ref, jnp.asarray(full)))[
        prompt_len - 1:prompt_len - 1 + n]


def firsts(lg, prompt_len: int, n: int) -> np.ndarray:
    """The token each served position's logits put first."""
    return np.asarray(jnp.argmax(lg, axis=-1))[
        prompt_len - 1:prompt_len - 1 + n].astype(np.int32)


def served_gaps(cfg: dict, params: dict, prompt: np.ndarray,
                served: np.ndarray) -> np.ndarray:
    """Gaps of ``served`` (the tokens the program emitted after
    ``prompt``) below the reference's best logit, one per token."""
    return gaps(cfg, logits(cfg, params, prompt, served), len(prompt),
                served)


def control_gaps(cfg: dict, params: dict, prompt: np.ndarray,
                 served: np.ndarray) -> np.ndarray:
    """Gaps, under the float32 reference, of the tokens the int8 control
    puts first at the served positions."""
    p, n = len(prompt), len(served)
    ctrl = firsts(logits(cfg, params, prompt, served, q8=True), p, n)
    return gaps(cfg, logits(cfg, params, prompt, served), p, ctrl)
