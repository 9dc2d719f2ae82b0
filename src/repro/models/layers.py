"""Shared neural building blocks (pure-functional, pytree params).

Conventions:
  * params are nested dicts of jnp arrays; leaves of per-layer blocks are
    stacked along a leading layer dim and consumed via ``lax.scan``.
  * all matmuls run in ``compute_dtype`` (bf16 on TPU); softmax/norms in f32.
  * key names are stable: the sharding rules in ``runtime/sharding.py``
    match on them.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(key, d, kind, dtype=jnp.float32):
    del key
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x, kind, eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def group_norm_heads(x, scale, eps=1e-5):
    """Per-head group norm used by RWKV6 wkv output.  x: [..., H, D]."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [B, S, H, D]; positions: [B, S] (or [S]) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(ang)[..., None, :]                   # [B, S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, chunked-query "flash" schedule)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h * hd, d), in_axis=0, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def _qkv(p, x, cfg):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (q.reshape(b, s, h, hd), k.reshape(b, s, hkv, hd), v.reshape(b, s, hkv, hd))


def chunked_attention(q, k, v, *, causal: bool, q_chunk: int = 1024,
                      positions_q=None, positions_k=None,
                      unroll: bool = False):
    """Memory-bounded attention: scan over query chunks, full softmax per
    chunk.  q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D].  GQA via head grouping.
    Never materializes the [Sq,Sk] score matrix for more than one chunk.
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    qc = min(q_chunk, sq)
    n_chunks = sq // qc
    assert sq % qc == 0, (sq, qc)

    qg = q.reshape(b, sq, hkv, g, hd)
    if positions_q is None:
        positions_q = jnp.arange(sq, dtype=jnp.int32)[None, :]
    if positions_k is None:
        positions_k = jnp.arange(sk, dtype=jnp.int32)[None, :]

    def one_chunk(carry, idx):
        qi = lax.dynamic_slice_in_dim(qg, idx * qc, qc, axis=1)      # [B,qc,Hkv,G,D]
        pq = lax.dynamic_slice_in_dim(positions_q, idx * qc, qc, axis=1)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = pq[:, None, None, :, None] >= positions_k[:, None, None, None, :]
            logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        oi = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return carry, oi.reshape(b, qc, h, hd)

    if n_chunks == 1:
        _, out = one_chunk(None, 0)
    else:
        _, chunks = lax.scan(one_chunk, None, jnp.arange(n_chunks),
                             unroll=unroll)
        out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, h, hd)
    return out


def attention_block(p, x, cfg, *, positions=None, q_chunk: int = 1024,
                    unroll: bool = False):
    """Full (training / prefill) attention incl. projections."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, axis=0)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=cfg.causal, q_chunk=q_chunk,
                            positions_q=positions, positions_k=positions,
                            unroll=unroll)
    return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)


def quantize_kv(x, axis=-1):
    """Symmetric per-token int8 quantization.  x: [..., D] float ->
    (int8 values, f32 scale[...])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_attention_q8(p, x, cfg, k_cache, v_cache, k_scale, v_scale,
                        index):
    """int8-KV variant of decode_attention (beyond-paper optimization:
    halves the dominant memory-term traffic of the D-Cache schedule).

    k_cache/v_cache: int8 [B, Hkv, S, D]; k_scale/v_scale: f32 [B, Hkv, S].
    """
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // hkv
    s = k_cache.shape[2]
    q, k, v = _qkv(p, x, cfg)
    pos = jnp.full((b, 1), index, dtype=jnp.int32)
    if cfg.rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    kq, ks = quantize_kv(jnp.swapaxes(k, 1, 2))          # [B,Hkv,1,D],[B,Hkv,1]
    vq, vs = quantize_kv(jnp.swapaxes(v, 1, 2))
    k_cache = lax.dynamic_update_slice(k_cache, kq, (0, 0, index, 0))
    v_cache = lax.dynamic_update_slice(v_cache, vq, (0, 0, index, 0))
    k_scale = lax.dynamic_update_slice(k_scale, ks, (0, 0, index))
    v_scale = lax.dynamic_update_slice(v_scale, vs, (0, 0, index))

    qg = q.reshape(b, hkv, g, hd)
    # dequantize to bf16 (f32 accumulate via preferred_element_type):
    # halves the materialized dequant traffic vs f32 copies (§Perf iter 3)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.bfloat16),
                        k_cache.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    logits = logits * k_scale[:, :, None, :] / math.sqrt(hd)
    valid = jnp.arange(s, dtype=jnp.int32)[None, None, None, :] <= index
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    pw = probs * v_scale[:, :, None, :]                   # fold dequant scale
    out = jnp.einsum("bhgs,bhsd->bhgd", pw.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(b, 1, h * hd) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache, k_scale, v_scale


def decode_attention(p, x, cfg, k_cache, v_cache, index):
    """One-token decode against a (possibly seq-sharded) KV cache.

    This is the paper-faithful "D-Cache" schedule: the KV cache stays put
    (sharded over the ``model`` axis = the storage pool), the query is
    broadcast, each shard computes a partial softmax and XLA emits only
    the tiny reduction collectives (log-sum-exp merge) — compute moves to
    the data, exactly the DockerSSD near-data principle.

    x: [B, 1, d]; k_cache/v_cache: [B, Hkv, S, D]; index: scalar int32.
    Returns (attn_out [B,1,d], new_k, new_v).
    """
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // hkv
    s = k_cache.shape[2]
    q, k, v = _qkv(p, x, cfg)                                   # [B,1,*,D]
    pos = jnp.full((b, 1), index, dtype=jnp.int32)
    if cfg.rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    # insert new kv at `index` (dynamic-update-slice: touches one page)
    k_new = jnp.swapaxes(k, 1, 2).astype(k_cache.dtype)         # [B,Hkv,1,D]
    v_new = jnp.swapaxes(v, 1, 2).astype(v_cache.dtype)
    k_cache = lax.dynamic_update_slice(k_cache, k_new, (0, 0, index, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v_new, (0, 0, index, 0))

    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(hd)
    valid = jnp.arange(s, dtype=jnp.int32)[None, None, None, :] <= index
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgs,bhsd->bhgd", probs, v_cache)
    out = out.reshape(b, 1, h * hd) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLP (gated / plain) and MoE
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":
        return {"w_up": dense_init(ks[0], (d, f), dtype=dtype),
                "b_up": jnp.zeros((f,), dtype),
                "w_down": dense_init(ks[1], (f, d), dtype=dtype),
                "b_down": jnp.zeros((d,), dtype)}
    return {"w_gate": dense_init(ks[0], (d, f), dtype=dtype),
            "w_up": dense_init(ks[1], (d, f), dtype=dtype),
            "w_down": dense_init(ks[2], (f, d), dtype=dtype)}


def _gate_act(x, act):
    if act in ("swiglu",):
        return jax.nn.silu(x)
    return jax.nn.gelu(x)  # geglu


def apply_mlp(p, x, act):
    if "w_gate" not in p:
        h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype) + p["b_up"].astype(x.dtype))
        return h @ p["w_down"].astype(x.dtype) + p["b_down"].astype(x.dtype)
    h = _gate_act(x @ p["w_gate"].astype(x.dtype), act) * (x @ p["w_up"].astype(x.dtype))
    return h @ p["w_down"].astype(x.dtype)


def init_moe(key, cfg, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), dtype=dtype),
        "w_gate": dense_init(ks[1], (e, d, f), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), in_axis=1, dtype=dtype),
    }


def apply_moe(p, x, cfg, capacity: Optional[int] = None,
              no_drop: bool = False):
    """Token-choice top-k MoE with capacity-bounded scatter dispatch.

    x: [B, S, d].  FLOPs scale with *active* params (top_k experts/token),
    not total — dispatch is a scatter into per-expert buffers, not a dense
    all-experts einsum.  ``no_drop`` sizes capacity to the worst case
    (exact routing; used for decode and correctness tests).
    Returns (out, aux_loss).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)
    if no_drop:
        capacity = t
    elif capacity is None:
        capacity = int(cfg.capacity_factor * t * k / e)
        capacity = max(capacity, 1)

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)                                  # [T,k]
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * (e ** 2) / e

    out = jnp.zeros((t, d), x.dtype)
    for j in range(k):
        eid = topi[:, j]                                              # [T]
        onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
        keep = pos < capacity
        slot = jnp.where(keep, pos, capacity)                         # overflow slot
        buf = jnp.zeros((e, capacity + 1, d), x.dtype)
        buf = buf.at[eid, slot].add(jnp.where(keep[:, None], xt, 0))
        buf = buf[:, :capacity]                                       # [E,C,d]
        # NOTE: we tried with_sharding_constraint hints (E->model,
        # C->data) here — measured WORSE (29 -> 107 GB/dev/layer of
        # collectives; GSPMD reshards the scatter).  The real fix is
        # apply_moe_shardmap below.  Kept dense dispatch as the
        # GSPMD-native baseline.
        h = _gate_act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype)),
                      cfg.act)
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
        y = jnp.concatenate([y, jnp.zeros((e, 1, d), y.dtype)], axis=1)
        gathered = y[eid, slot]                                       # [T,d]
        out = out + gathered * topv[:, j:j + 1].astype(x.dtype)
    return out.reshape(b, s, d), aux


def apply_moe_shardmap(p, x, cfg, no_drop: bool = False):
    """Explicit-schedule MoE (beyond-paper hillclimb, EXPERIMENTS.md §Perf).

    GSPMD's dense-dispatch partitioning all-gathers the [E, C, d] expert
    buffers over the data axis (~8.4 GB/dev/layer measured on
    phi3.5-moe).  This shard_map version never materializes a global
    capacity buffer:

      * routing + dispatch run on each data shard's LOCAL tokens with
        LOCAL capacity (no communication);
      * each model shard slices out ITS experts (weights arrive via one
        bf16 FSDP all-gather) and runs the FFN on every data shard's
        local buffer;
      * the combine is a single psum over `model` of the [T_local, d]
        outputs — the only activation collective in the layer.

    Requires n_experts % model-axis == 0; falls back to ``apply_moe``
    outside a mesh context.
    """
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty or "model" not in m.axis_names:
        return apply_moe(p, x, cfg, no_drop=no_drop)
    from jax.sharding import PartitionSpec as P
    e, k = cfg.n_experts, cfg.top_k
    d, f = cfg.d_model, cfg.d_ff
    tp = m.shape["model"]
    if e % tp != 0:
        return apply_moe(p, x, cfg, no_drop=no_drop)
    e_loc = e // tp
    fsdp = tuple(a for a in ("pod", "data") if a in m.axis_names)
    fa = fsdp if len(fsdp) > 1 else fsdp[0]
    b, s, _ = x.shape

    pspecs = {"router": P(None, None),
              "w_gate": P("model", fa, None),
              "w_up": P("model", fa, None),
              "w_down": P("model", fa, None)}
    xspec = P(fa, None, None)

    def local_fn(pp, xx):
        t = xx.shape[0] * s
        xt = xx.reshape(t, d)
        # one bf16 FSDP gather per weight (the standard ZeRO cost)
        wg = lax.all_gather(pp["w_gate"].astype(xt.dtype), fsdp, axis=1,
                            tiled=True)
        wu = lax.all_gather(pp["w_up"].astype(xt.dtype), fsdp, axis=1,
                            tiled=True)
        wd = lax.all_gather(pp["w_down"].astype(xt.dtype), fsdp, axis=1,
                            tiled=True)
        logits = (xt @ pp["router"].astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        capacity = t if no_drop else max(
            int(cfg.capacity_factor * t * k / e), 1)
        my0 = lax.axis_index("model") * e_loc

        out = jnp.zeros((t, d), xt.dtype)
        for j in range(k):
            eid = topi[:, j]
            onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)
            pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
            keep = pos < capacity
            slot = jnp.where(keep, pos, capacity)
            buf = jnp.zeros((e, capacity + 1, d), xt.dtype)
            buf = buf.at[eid, slot].add(jnp.where(keep[:, None], xt, 0))
            mine = lax.dynamic_slice_in_dim(buf[:, :capacity], my0, e_loc, 0)
            h = _gate_act(jnp.einsum("ecd,edf->ecf", mine, wg), cfg.act)
            h = h * jnp.einsum("ecd,edf->ecf", mine, wu)
            y = jnp.einsum("ecf,efd->ecd", h, wd)          # [e_loc, C, d]
            y = jnp.concatenate([y, jnp.zeros((e_loc, 1, d), y.dtype)], 1)
            sel = keep & (eid >= my0) & (eid < my0 + e_loc)
            gathered = y[jnp.clip(eid - my0, 0, e_loc - 1), slot]
            gathered = jnp.where(sel[:, None], gathered, 0)
            out = out + gathered * topv[:, j:j + 1].astype(xt.dtype)
        out = lax.psum(out, "model")
        density = jnp.mean(jax.nn.one_hot(topi[:, 0], e,
                                          dtype=jnp.float32), 0)
        density_proxy = jnp.mean(probs, axis=0)
        aux = jnp.sum(density * density_proxy) * e
        aux = lax.pmean(aux, fsdp)
        return out.reshape(xx.shape), aux

    fn = jax.shard_map(local_fn, mesh=m, in_specs=(pspecs, xspec),
                       out_specs=(xspec, P()), check_vma=False)
    return fn({kk: p[kk] for kk in ("router", "w_gate", "w_up", "w_down")},
              x)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(key, cfg, dtype=jnp.float32):
    return {"table": embed_init(key, (cfg.vocab_size, cfg.d_model), dtype)}


def embed_tokens(p, tokens, compute_dtype):
    return jnp.take(p["table"], tokens, axis=0).astype(compute_dtype)


def unembed(p_embed, p_head, x, tie: bool):
    if tie:
        w = p_embed["table"].astype(x.dtype).T
    else:
        w = p_head["w"].astype(x.dtype)
    return (x @ w).astype(jnp.float32)


def cross_entropy(logits, labels, ignore_id: int = -1):
    """Mean next-token CE in f32.  logits: [..., V] f32; labels int32."""
    mask = (labels != ignore_id).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].clip(0), axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
