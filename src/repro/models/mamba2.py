"""Mamba2 (SSD) blocks + the Zamba2 hybrid LM (Mamba2 backbone with a
*shared*, weight-tied attention block applied every ``attn_every``
layers — arXiv:2411.15242).

The SSD scan uses the chunked parallel form with scalar per-head decay;
every exponent is a difference of cumulative log-decays (<= 0, f32-safe).
Decode is O(1)-state recurrent, so zamba2 runs ``long_500k``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L

D_CONV = 4


# ---------------------------------------------------------------------------
# SSD chunked scan (scalar decay per head)
# ---------------------------------------------------------------------------


def ssd_chunk(cb, x, dt, da, h0):
    """One chunk, one (batch, head).

    cb: tuple (C, B) each [Ck, ds]; x: [Ck, dh]; dt: [Ck]; da: [Ck] (<=0);
    h0: [dh, ds].  Returns (y [Ck, dh], hC).
    """
    Cm, Bm = cb
    ck = x.shape[0]
    cum = jnp.cumsum(da)                                  # [Ck]
    decay = cum[:, None] - cum[None, :]                   # t, s
    mask = jnp.tril(jnp.ones((ck, ck), bool))
    # mask BEFORE exp: exp of (positive) upper-triangle entries would
    # overflow and poison gradients via inf * 0
    dmat = jnp.exp(jnp.where(mask, decay, -jnp.inf))      # [t, s]
    scores = (Cm @ Bm.T) * dmat                           # [t, s]
    xin = x * dt[:, None]                                 # [Ck, dh]
    y = scores @ xin                                      # [Ck, dh]
    # initial state contribution
    y = y + jnp.exp(cum)[:, None] * (Cm @ h0.T)           # [Ck, dh]
    # state update
    w = jnp.exp(cum[-1] - cum)                            # [Ck]
    hC = jnp.exp(cum[-1]) * h0 + jnp.einsum("c,cd,cs->ds", w, xin, Bm)
    return y, hC


def ssd_chunked(x, dt, da, Bm, Cm, h0, chunk: int = 64,
                unroll: bool = False):
    """x: [B,S,H,dh]; dt/da: [B,S,H]; Bm/Cm: [B,S,ds]; h0: [B,H,dh,ds].
    Returns (y [B,S,H,dh], hT)."""
    b, s, h, dh = x.shape
    ds = Bm.shape[-1]
    ck = min(chunk, s)
    assert s % ck == 0
    n = s // ck

    xs_x = jnp.moveaxis(x.reshape(b, n, ck, h, dh), (1, 3), (0, 2))   # [N,B,H,Ck,dh]
    xs_dt = jnp.moveaxis(dt.reshape(b, n, ck, h), (1, 3), (0, 2))     # [N,B,H,Ck]
    xs_da = jnp.moveaxis(da.reshape(b, n, ck, h), (1, 3), (0, 2))
    xs_B = jnp.moveaxis(Bm.reshape(b, n, ck, ds), 1, 0)               # [N,B,Ck,ds]
    xs_C = jnp.moveaxis(Cm.reshape(b, n, ck, ds), 1, 0)

    # vmap over batch then head (B/C shared across heads)
    f = jax.vmap(ssd_chunk, in_axes=((None, None), 0, 0, 0, 0))       # heads
    f = jax.vmap(f, in_axes=((0, 0), 0, 0, 0, 0))                     # batch

    def body(state, xs):
        xc, dtc, dac, bc, cc = xs
        y, state = f((cc, bc), xc, dtc, dac, state)
        return state, y

    hT, ys = lax.scan(body, h0, (xs_x, xs_dt, xs_da, xs_B, xs_C),
                      unroll=unroll)
    y = jnp.moveaxis(ys, (0, 2), (1, 3)).reshape(b, s, h, dh)
    return y, hT


def ssd_step(x, dt, da, Bm, Cm, state):
    """One token.  x: [B,H,dh]; dt/da: [B,H]; Bm/Cm: [B,ds];
    state: [B,H,dh,ds]."""
    xin = x * dt[..., None]                                # [B,H,dh]
    new = jnp.exp(da)[..., None, None] * state + \
        xin[..., :, None] * Bm[:, None, None, :]
    y = jnp.einsum("bhds,bs->bhd", new, Cm)
    return y, new


def ssd_ref(x, dt, da, Bm, Cm, h0):
    """Naive per-token oracle."""
    def body(state, xs):
        xt, dtt, dat, bt, ct = xs
        y, state = ssd_step(xt, dtt, dat, bt, ct, state)
        return state, y
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, da, Bm, Cm))
    hT, ys = lax.scan(body, h0, xs)
    return jnp.moveaxis(ys, 0, 1), hT


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def mamba2_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state
    return d_inner, n_heads, conv_dim


def init_mamba2(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    d_inner, n_heads, conv_dim = mamba2_dims(cfg)
    d_proj = 2 * d_inner + 2 * cfg.ssm_state + n_heads
    ks = jax.random.split(key, 4)
    return {
        "in_proj": L.dense_init(ks[0], (d, d_proj), dtype=dtype),
        "conv_w": (0.1 * jax.random.normal(ks[1], (D_CONV, conv_dim))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 8.0, n_heads)).astype(dtype),
        "d_skip": jnp.ones((n_heads,), dtype),
        "dt_bias": jnp.zeros((n_heads,), dtype),
        "gate_norm": {"scale": jnp.ones((d_inner,), dtype)},
        "out_proj": L.dense_init(ks[2], (d_inner, d), dtype=dtype),
    }


def _split_proj(cfg, zxbcdt):
    d_inner, n_heads, _ = mamba2_dims(cfg)
    ds = cfg.ssm_state
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * ds], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, w, bias):
    """Depthwise causal conv over seq.  xBC: [B,S,C]; w: [D_CONV, C]."""
    pad = jnp.pad(xBC, ((0, 0), (D_CONV - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(D_CONV))
    return jax.nn.silu(out + bias[None, None, :])


def apply_mamba2_seq(p, x, cfg, conv_state, ssm_state, chunk=64,
                     unroll=False, eps=1e-6, n_valid=None):
    """x: [B,S,d].  Returns (out, new_conv_state, new_ssm_state).

    ``n_valid`` (a traced scalar) marks positions ``>= n_valid`` as
    padding: their dt is 0, so they neither decay nor feed the state,
    and the conv tail is the last ``D_CONV - 1`` inputs before it."""
    b, s, d = x.shape
    d_inner, n_heads, conv_dim = mamba2_dims(cfg)
    ds, dh = cfg.ssm_state, cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    # prepend carried conv inputs (for prefill continuity)
    full = jnp.concatenate([conv_state.astype(xBC.dtype), xBC], axis=1)
    xBC_conv = _causal_conv(full, p["conv_w"].astype(x.dtype),
                            p["conv_b"].astype(x.dtype))[:, D_CONV - 1:]
    if n_valid is None:
        new_conv_state = full[:, -(D_CONV - 1):]
    else:
        new_conv_state = lax.dynamic_slice_in_dim(full, n_valid,
                                                  D_CONV - 1, axis=1)
    xs, Bm, Cm = jnp.split(xBC_conv, [d_inner, d_inner + ds], axis=-1)
    xs = xs.reshape(b, s, n_heads, dh)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))      # [B,S,H]
    if n_valid is not None:
        dt = jnp.where((jnp.arange(s) < n_valid)[None, :, None], dt, 0.0)
    da = -jnp.exp(p["a_log"].astype(jnp.float32))[None, None, :] * dt
    y, hT = ssd_chunked(xs.astype(jnp.float32), dt, da,
                        Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                        ssm_state, chunk=chunk, unroll=unroll)
    y = y + xs.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    return mamba2_out(p, y, z, eps), new_conv_state, hT


def mamba2_out(p, y, z, eps=1e-6):
    """Gated RMSNorm of ``y`` by ``silu(z)``, then ``out_proj``."""
    y = L.apply_norm(p["gate_norm"], y * jax.nn.silu(z), "rmsnorm", eps)
    return y @ p["out_proj"].astype(y.dtype)


def mamba2_step_in(p, x, cfg, conv_state):
    """The inputs of one token's state update.  x: [B,d];
    conv_state: [B, D_CONV-1, conv_dim].  Returns (z, xs [B,H,dh] f32,
    dt [B,H] f32 after softplus, Bm, Cm [B,ds] f32, new_conv_state)."""
    b, d = x.shape
    d_inner, n_heads, conv_dim = mamba2_dims(cfg)
    ds, dh = cfg.ssm_state, cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    window = jnp.concatenate([conv_state.astype(xBC.dtype), xBC[:, None, :]],
                             axis=1)                             # [B,D_CONV,C]
    conv = jnp.sum(window * p["conv_w"].astype(x.dtype)[None], axis=1)
    xBC_c = jax.nn.silu(conv + p["conv_b"].astype(x.dtype))
    xs, Bm, Cm = jnp.split(xBC_c, [d_inner, d_inner + ds], axis=-1)
    xs = xs.reshape(b, n_heads, dh).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return (z, xs, dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
            window[:, 1:])


def apply_mamba2_step(p, x, cfg, conv_state, ssm_state):
    """x: [B,d] one token.  conv_state: [B, D_CONV-1, conv_dim]."""
    b, d = x.shape
    d_inner = mamba2_dims(cfg)[0]
    z, xs, dt, Bm, Cm, new_conv_state = mamba2_step_in(p, x, cfg,
                                                       conv_state)
    da = -jnp.exp(p["a_log"].astype(jnp.float32))[None, :] * dt
    y, new_ssm = ssd_step(xs, dt, da, Bm, Cm, ssm_state)
    y = y + xs * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, d_inner).astype(x.dtype)
    return mamba2_out(p, y, z), new_conv_state, new_ssm


# ---------------------------------------------------------------------------
# Zamba2 hybrid LM
# ---------------------------------------------------------------------------


class Zamba2LM:
    """Mamba2 backbone; ONE shared attention+MLP block applied before every
    ``attn_every``-th mamba layer (weight-tied across its applications,
    each application keeping its own KV cache)."""

    def __init__(self, cfg, compute_dtype=jnp.bfloat16, chunk: int = 64,
                 remat: str = "full", loss_chunk: int = 256,
                 q_chunk: int = 1024, unroll_inner: bool = False):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.chunk = chunk
        self.remat = remat
        self.q_chunk = q_chunk
        self.unroll = unroll_inner
        self.groups = []
        i = 0
        while i < cfg.n_layers:
            self.groups.append((i, min(i + cfg.attn_every, cfg.n_layers)))
            i += cfg.attn_every
        self.n_attn = len(self.groups)

    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(rng, 6)
        layer_keys = jax.random.split(ks[0], cfg.n_layers)

        def init_layer(key):
            k1, k2 = jax.random.split(key)
            return {"norm": L.init_norm(k1, cfg.d_model, "rmsnorm", dtype),
                    "mamba": init_mamba2(k2, cfg, dtype)}

        shared = {
            "attn_norm": L.init_norm(ks[1], cfg.d_model, "rmsnorm", dtype),
            "attn": L.init_attention(ks[2], cfg, dtype),
            "mlp_norm": L.init_norm(ks[1], cfg.d_model, "rmsnorm", dtype),
            "mlp": L.init_mlp(ks[3], cfg, dtype),
        }
        return {
            "embed": L.init_embed(ks[4], cfg, dtype),
            "shared_attn": shared,
            "layers": jax.vmap(init_layer)(layer_keys),
            "final_norm": L.init_norm(ks[1], cfg.d_model, "rmsnorm", dtype),
            "lm_head": {"w": L.dense_init(ks[5], (cfg.d_model, cfg.vocab_size),
                                          dtype=dtype)},
        }

    # -- shared attention block ----------------------------------------------

    def _shared_attn_seq(self, sp, h, positions, cache_dtype):
        cfg = self.cfg
        b, s, _ = h.shape
        a = L.apply_norm(sp["attn_norm"], h, "rmsnorm")
        q, k, v = L._qkv(sp["attn"], a, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.chunked_attention(q, k, v, causal=True, q_chunk=self.q_chunk,
                                positions_q=positions, positions_k=positions,
                                unroll=self.unroll)
        h = h + o.reshape(b, s, -1) @ sp["attn"]["wo"].astype(h.dtype)
        m = L.apply_norm(sp["mlp_norm"], h, "rmsnorm")
        h = h + L.apply_mlp(sp["mlp"], m, cfg.act)
        kc = jnp.swapaxes(k, 1, 2).astype(cache_dtype)
        vc = jnp.swapaxes(v, 1, 2).astype(cache_dtype)
        return h, (kc, vc)

    def _shared_attn_step(self, sp, h, kc, vc, index):
        cfg = self.cfg
        a = L.apply_norm(sp["attn_norm"], h, "rmsnorm")
        o, kc, vc = L.decode_attention(sp["attn"], a, cfg, kc, vc, index)
        h = h + o
        m = L.apply_norm(sp["mlp_norm"], h, "rmsnorm")
        h = h + L.apply_mlp(sp["mlp"], m, cfg.act)
        return h, kc, vc

    # -- full forward ----------------------------------------------------------

    def _run(self, params, h, state, cache_dtype=jnp.bfloat16):
        """Sequence forward; returns (h, new_state)."""
        cfg = self.cfg
        b, s, _ = h.shape
        start = state["index"]
        positions = (start + jnp.arange(s, dtype=jnp.int32))[None, :].repeat(b, 0)
        kcs, vcs, convs, ssms = [], [], [], []
        mamba_fn = lambda hh, lp, cs, ss: self._mamba_layer(hh, lp, cs, ss)
        if self.remat != "none":
            mamba_fn = jax.checkpoint(mamba_fn)
        for g, (lo, hi) in enumerate(self.groups):
            h, (kc, vc) = self._shared_attn_seq(params["shared_attn"], h,
                                                positions, cache_dtype)
            kcs.append(kc)
            vcs.append(vc)
            sub = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            cs0 = state["conv"][lo:hi]
            ss0 = state["ssm"][lo:hi]

            def body(hh, xs):
                lp, cs, ss = xs
                return mamba_fn(hh, lp, cs, ss)

            h, (ncs, nss) = lax.scan(body, h, (sub, cs0, ss0),
                                     unroll=self.unroll)
            convs.append(ncs)
            ssms.append(nss)
        h = L.apply_norm(params["final_norm"], h, "rmsnorm")
        d_inner, n_heads, conv_dim = mamba2_dims(cfg)
        new_state = {
            "k": (jnp.stack(kcs) if kcs else
                  jnp.zeros((0, b, cfg.n_kv_heads, s, cfg.hd), cache_dtype)),
            "v": (jnp.stack(vcs) if vcs else
                  jnp.zeros((0, b, cfg.n_kv_heads, s, cfg.hd), cache_dtype)),
            "conv": (jnp.concatenate(convs, axis=0) if convs else
                     state["conv"]),
            "ssm": (jnp.concatenate(ssms, axis=0) if ssms else state["ssm"]),
            "index": start + s,
        }
        return h, new_state

    def _mamba_layer(self, h, lp, conv_state, ssm_state):
        a = L.apply_norm(lp["norm"], h, "rmsnorm")
        o, ncs, nss = apply_mamba2_seq(lp["mamba"], a, self.cfg, conv_state,
                                       ssm_state, chunk=self.chunk,
                                       unroll=self.unroll)
        return h + o, (ncs, nss)

    def _state0(self, b, seq_hint: int = 0, cache_dtype=jnp.bfloat16):
        cfg = self.cfg
        d_inner, n_heads, conv_dim = mamba2_dims(cfg)
        s = max(seq_hint, 1)
        return {
            "k": jnp.zeros((self.n_attn, b, cfg.n_kv_heads, s, cfg.hd),
                           cache_dtype),
            "v": jnp.zeros((self.n_attn, b, cfg.n_kv_heads, s, cfg.hd),
                           cache_dtype),
            "conv": jnp.zeros((cfg.n_layers, b, D_CONV - 1, conv_dim),
                              jnp.float32),
            "ssm": jnp.zeros((cfg.n_layers, b, n_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
            "index": jnp.zeros((), jnp.int32),
        }

    def forward(self, params, batch):
        h = L.embed_tokens(params["embed"], batch["tokens"], self.compute_dtype)
        state = self._state0(h.shape[0], h.shape[1], self.compute_dtype)
        h, _ = self._run(params, h, state)
        logits = (h @ params["lm_head"]["w"].astype(h.dtype)).astype(jnp.float32)
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch)
        ce = L.cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": jnp.zeros(())}

    # -- serving ---------------------------------------------------------------

    def cache_spec(self, batch: int, seq: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        d_inner, n_heads, conv_dim = mamba2_dims(cfg)
        return {
            "k": jax.ShapeDtypeStruct(
                (self.n_attn, batch, cfg.n_kv_heads, seq, cfg.hd), dtype),
            "v": jax.ShapeDtypeStruct(
                (self.n_attn, batch, cfg.n_kv_heads, seq, cfg.hd), dtype),
            "conv": jax.ShapeDtypeStruct(
                (cfg.n_layers, batch, D_CONV - 1, conv_dim), jnp.float32),
            "ssm": jax.ShapeDtypeStruct(
                (cfg.n_layers, batch, n_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), jnp.float32),
            "index": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16):
        spec = self.cache_spec(batch, seq, dtype)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def prefill(self, params, batch, cache_dtype=jnp.bfloat16):
        h = L.embed_tokens(params["embed"], batch["tokens"], self.compute_dtype)
        state = self._state0(h.shape[0], h.shape[1], cache_dtype)
        h, state = self._run(params, h, state, cache_dtype)
        logits = (h[:, -1] @ params["lm_head"]["w"].astype(h.dtype)).astype(
            jnp.float32)
        return logits, state

    def decode_step(self, params, cache, tokens):
        cfg = self.cfg
        index = cache["index"]
        h = L.embed_tokens(params["embed"], tokens[:, None],
                           self.compute_dtype)                    # [B,1,d]
        kcs, vcs, convs, ssms = [], [], [], []
        for g, (lo, hi) in enumerate(self.groups):
            h, kc, vc = self._shared_attn_step(
                params["shared_attn"], h, cache["k"][g], cache["v"][g], index)
            kcs.append(kc)
            vcs.append(vc)
            sub = jax.tree.map(lambda a: a[lo:hi], params["layers"])

            def body(hh, xs):
                lp, cs, ss = xs
                a = L.apply_norm(lp["norm"], hh, "rmsnorm")
                o, ncs, nss = apply_mamba2_step(lp["mamba"], a[:, 0], cfg,
                                                cs, ss)
                return hh + o[:, None, :], (ncs, nss)

            h, (ncs, nss) = lax.scan(
                body, h, (sub, cache["conv"][lo:hi], cache["ssm"][lo:hi]),
                unroll=self.unroll)
            convs.append(ncs)
            ssms.append(nss)
        h = L.apply_norm(params["final_norm"], h, "rmsnorm")
        logits = (h[:, 0] @ params["lm_head"]["w"].astype(h.dtype)).astype(
            jnp.float32)
        new_cache = {
            "k": jnp.stack(kcs) if kcs else cache["k"],
            "v": jnp.stack(vcs) if vcs else cache["v"],
            "conv": jnp.concatenate(convs, axis=0) if convs else cache["conv"],
            "ssm": jnp.concatenate(ssms, axis=0) if ssms else cache["ssm"],
            "index": index + 1,
        }
        return logits, new_cache
