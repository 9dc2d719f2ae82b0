"""Probe-based roofline measurement (component probes).

XLA's HLO cost analysis (a) counts a ``while`` (lax.scan) body once and
(b) reports **per-device** numbers for SPMD modules.  The full-model
compile therefore cannot supply roofline terms.  Instead we compile
tiny *component* modules on the production mesh with pinned shardings
and compose:

  train:   ga * (L * layer_vjp + tail_vjp) + opt_update
  serve:   L * layer_fwd + tail_fwd            (prefill / decode)
  hybrid:  L_mamba * mamba_layer + N_attn * shared_attn + tail

Each component is a real compiled artifact: collectives included, remat
policy identical to the production step (vjp through jax.checkpoint).
All numbers are per-device; the roofline formulas divide by per-chip
peaks, which is equivalent to global/(chips*peak).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, get_arch
from repro.models import layers as LYR
from repro.models.api import get_model
from repro.optim import adamw, warmup_cosine
from repro.optim.adamw import clip_by_global_norm
from repro.runtime import roofline, sharding as shd

KEYS = ("flops", "bytes", "coll")


def _measure(lowered) -> Dict[str, float]:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = roofline.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(v for k, v in coll.items() if k != "count")),
    }


def _sum(*costs, weights=None) -> Dict[str, float]:
    weights = weights or [1.0] * len(costs)
    return {k: sum(w * c[k] for w, c in zip(weights, costs)) for k in KEYS}


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _layer_param_probe(cfg, mesh, model, stacked):
    """(specs, shardings) for ONE layer's params from the stacked tree."""
    def strip(path, leaf):
        return jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
    one = jax.tree_util.tree_map_with_path(strip, stacked)

    def spec_of(path, leaf):
        keys = ("layers",) + tuple(shd._key_of(p) for p in path)
        sp = shd.param_spec(mesh, keys, (1,) + leaf.shape)
        return P(*sp[1:])
    specs = jax.tree_util.tree_map_with_path(spec_of, one)
    return one, jax.tree.map(lambda s: _ns(mesh, s), specs)


def _h_sharding(mesh, b):
    ba = shd.batch_axes(mesh)
    sb = shd._ax(mesh, b, *ba)
    return _ns(mesh, P(sb, None, None))


# ---------------------------------------------------------------------------
# transformer probes
# ---------------------------------------------------------------------------


def _tfm_layer_train(model, mesh, b, s, opt: int = 0):
    cfg = model.cfg
    impl = model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    h_spec = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    hs = _h_sharding(mesh, b)
    positions = None

    layer = impl._maybe_remat(lambda hh, lp: impl._layer(
        hh, lp, jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, axis=0)))

    def cast(lp):
        if opt < 1:
            return lp
        # bf16 FSDP gathers: cast the sharded master weight BEFORE use
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if (x.ndim >= 2 and x.dtype == jnp.float32) else x, lp)

    def f(lp, h, ct):
        (y, aux), vjp = jax.vjp(
            lambda lp_, h_: layer(h_, cast(lp_)), lp, h)
        glp, gh = vjp((ct, jnp.ones((), jnp.float32)))
        return glp, gh

    lowered = jax.jit(f, in_shardings=(lp_shard, hs, hs),
                      out_shardings=(lp_shard, hs)).lower(
        lp_shape, h_spec, h_spec)
    return _measure(lowered)


def _tfm_tail_train(model, mesh, mb_specs):
    """0-layer model loss grad = embed + final norm + chunked CE."""
    cfg = model.cfg
    zero = dataclasses.replace(cfg, n_layers=0)
    zm = get_model(zero, compute_dtype=jnp.bfloat16, remat="full",
                   unroll_inner=True)
    params_shape = jax.eval_shape(zm.init, jax.random.PRNGKey(0))
    pshard = jax.tree.map(lambda sp: _ns(mesh, sp),
                          shd.param_specs(mesh, params_shape))
    bshard = shd.to_shardings(mesh, shd.batch_spec(mesh, mb_specs))

    def grad_fn(p, bb):
        (loss, _), g = jax.value_and_grad(zm.loss, has_aux=True)(p, bb)
        return g, loss

    lowered = jax.jit(grad_fn, in_shardings=(pshard, bshard),
                      out_shardings=(pshard, _ns(mesh, P()))).lower(
        params_shape, mb_specs)
    return _measure(lowered)


def _serve_tail(model, mesh, shape, kind, opt: int = 0):
    cfg = model.cfg
    zero = dataclasses.replace(cfg, n_layers=0,
                               attn_every=cfg.attn_every or 0)
    kw = ({"kv_quant": model.impl.kv_quant}
          if cfg.block_type == "transformer" else {})
    zm = get_model(zero, compute_dtype=jnp.bfloat16, unroll_inner=True, **kw)
    params_shape = jax.eval_shape(zm.init, jax.random.PRNGKey(0))
    if opt >= 1:
        params_shape = shd.cast_float_specs(params_shape, jnp.bfloat16)
        pshard = jax.tree.map(lambda sp: _ns(mesh, sp),
                              shd.serve_param_specs(mesh, params_shape))
    else:
        pshard = jax.tree.map(lambda sp: _ns(mesh, sp),
                              shd.param_specs(mesh, params_shape))
    in_specs = zm.input_specs(shape)
    if kind == "prefill":
        bshard = shd.to_shardings(mesh, shd.batch_spec(mesh, in_specs))
        if cfg.encoder_only:
            fn = lambda p, bb: zm.forward(p, bb)[0]
            lowered = jax.jit(fn, in_shardings=(pshard, bshard)).lower(
                params_shape, in_specs)
        else:
            lowered = jax.jit(
                zm.prefill, in_shardings=(pshard, bshard)).lower(
                params_shape, in_specs)
    else:
        cache_spec = in_specs["cache"]
        cshard = shd.to_shardings(mesh,
                                  shd.cache_spec_shardings(mesh, cache_spec))
        tshard = _ns(mesh, shd.decode_token_spec(mesh, shape.global_batch))
        lowered = jax.jit(zm.decode_step,
                          in_shardings=(pshard, cshard, tshard),
                          donate_argnums=(1,)).lower(
            params_shape, cache_spec, in_specs["tokens"])
    return _measure(lowered)


def _kv_shard(mesh, b, s):
    sb = shd._ax(mesh, b, "data")
    seq_axes = ("pod", "model") if "pod" in mesh.axis_names else ("model",)
    ss = shd._ax(mesh, s, *seq_axes)
    return _ns(mesh, P(sb, None, ss, None))


def _tfm_layer_prefill(model, mesh, b, s):
    cfg = model.cfg
    impl = model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    h_spec = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    hs = _h_sharding(mesh, b)
    kv_spec = jax.ShapeDtypeStruct((b, cfg.n_kv_heads, s, cfg.hd),
                                   jnp.bfloat16)
    kvs = _kv_shard(mesh, b, s)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    def f(lp, h):
        a = LYR.apply_norm(lp["attn_norm"], h, cfg.norm)
        q, k, v = LYR._qkv(lp["attn"], a, cfg)
        pos = positions.repeat(b, axis=0)
        if cfg.rope:
            q = LYR.apply_rope(q, pos, cfg.rope_theta)
            k = LYR.apply_rope(k, pos, cfg.rope_theta)
        o = LYR.chunked_attention(q, k, v, causal=cfg.causal,
                                  q_chunk=impl.q_chunk, unroll=True)
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"].astype(h.dtype)
        m = LYR.apply_norm(lp["mlp_norm"], h, cfg.norm)
        if cfg.is_moe:
            mo, _ = LYR.apply_moe(lp["mlp"], m, cfg)
        else:
            mo = LYR.apply_mlp(lp["mlp"], m, cfg.act)
        kc = jnp.swapaxes(k, 1, 2).astype(jnp.bfloat16)
        vc = jnp.swapaxes(v, 1, 2).astype(jnp.bfloat16)
        return h + mo, kc, vc

    lowered = jax.jit(f, in_shardings=(lp_shard, hs),
                      out_shardings=(hs, kvs, kvs)).lower(lp_shape, h_spec)
    return _measure(lowered)


def _tfm_layer_decode(model, mesh, b, s, opt: int = 0):
    cfg = model.cfg
    impl = model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    if opt >= 1:   # bf16 weights, TP-only (no per-token FSDP gather)
        lp_shape = shd.cast_float_specs(lp_shape, jnp.bfloat16)
        fa = set(shd.fsdp_axes(mesh))

        def strip(spec):
            def keep(ax):
                if isinstance(ax, tuple):
                    kept = tuple(a for a in ax if a not in fa)
                    return (kept if len(kept) > 1 else
                            (kept[0] if kept else None))
                return None if ax in fa else ax
            return P(*(keep(ax) for ax in spec.spec))
        lp_shard = jax.tree.map(lambda ns: _ns(mesh, strip(ns)), lp_shard)
    q8 = opt >= 2 and impl.kv_quant == "int8"
    h_spec = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
    hs = _h_sharding(mesh, b)
    kv_dtype = jnp.int8 if q8 else jnp.bfloat16
    kv_spec = jax.ShapeDtypeStruct((b, cfg.n_kv_heads, s, cfg.hd), kv_dtype)
    kvs = _kv_shard(mesh, b, s)
    sc_spec = jax.ShapeDtypeStruct((b, cfg.n_kv_heads, s), jnp.float32)
    scs = _ns(mesh, P(shd._ax(mesh, b, "data"), None,
                      shd._ax(mesh, s, "model")))

    if q8:
        def f(lp, h, kc, vc, ksc, vsc, index):
            a = LYR.apply_norm(lp["attn_norm"], h, cfg.norm)
            o, kc, vc, ksc, vsc = LYR.decode_attention_q8(
                lp["attn"], a, cfg, kc, vc, ksc, vsc, index)
            h = h + o
            m = LYR.apply_norm(lp["mlp_norm"], h, cfg.norm)
            if cfg.is_moe:
                mo, _ = LYR.apply_moe(lp["mlp"], m, cfg, no_drop=True)
            else:
                mo = LYR.apply_mlp(lp["mlp"], m, cfg.act)
            return h + mo, kc, vc, ksc, vsc

        lowered = jax.jit(
            f, in_shardings=(lp_shard, hs, kvs, kvs, scs, scs,
                             _ns(mesh, P())),
            out_shardings=(hs, kvs, kvs, scs, scs),
            donate_argnums=(2, 3, 4, 5)).lower(
            lp_shape, h_spec, kv_spec, kv_spec, sc_spec, sc_spec,
            jax.ShapeDtypeStruct((), jnp.int32))
        return _measure(lowered)

    def f(lp, h, kc, vc, index):
        a = LYR.apply_norm(lp["attn_norm"], h, cfg.norm)
        o, kc, vc = LYR.decode_attention(lp["attn"], a, cfg, kc, vc, index)
        h = h + o
        m = LYR.apply_norm(lp["mlp_norm"], h, cfg.norm)
        if cfg.is_moe:
            mo, _ = LYR.apply_moe(lp["mlp"], m, cfg, no_drop=True)
        else:
            mo = LYR.apply_mlp(lp["mlp"], m, cfg.act)
        return h + mo, kc, vc

    lowered = jax.jit(
        f, in_shardings=(lp_shard, hs, kvs, kvs, _ns(mesh, P())),
        out_shardings=(hs, kvs, kvs), donate_argnums=(2, 3)).lower(
        lp_shape, h_spec, kv_spec, kv_spec,
        jax.ShapeDtypeStruct((), jnp.int32))
    return _measure(lowered)


# ---------------------------------------------------------------------------
# rwkv probes
# ---------------------------------------------------------------------------


def _rwkv_states(cfg, impl, b, kind):
    d = cfg.d_model
    return (jax.ShapeDtypeStruct((b, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, impl.n_heads, impl.dk, impl.dk),
                                 jnp.float32))


def _rwkv_state_shardings(mesh, b, impl):
    sb = shd._ax(mesh, b, "data")
    return (_ns(mesh, P(sb, "model")), _ns(mesh, P(sb, "model")),
            _ns(mesh, P(sb, None, None, None)))


def _rwkv_layer(model, mesh, b, s, train: bool):
    cfg, impl = model.cfg, model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    st_tm, st_cm, wkv = _rwkv_states(cfg, impl, b, "seq")
    st_sh = _rwkv_state_shardings(mesh, b, impl)
    h_spec = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    hs = _h_sharding(mesh, b)

    def layer(hh, lp, a_tm, a_cm, a_wkv):
        x = LYR.apply_norm(lp["ln1"], hh, "layernorm")
        o, n_tm, n_wkv = impl._time_mix_seq(lp["time_mix"], x,
                                            a_tm.astype(x.dtype), a_wkv)
        hh = hh + o
        c = LYR.apply_norm(lp["ln2"], hh, "layernorm")
        o2, n_cm = impl._channel_mix_seq(lp["channel_mix"], c,
                                         a_cm.astype(c.dtype))
        return hh + o2, n_tm.astype(jnp.bfloat16), n_cm.astype(jnp.bfloat16), n_wkv

    if train:
        layer_r = jax.checkpoint(layer)

        def f(lp, h, ct, a_tm, a_cm, a_wkv):
            outs, vjp = jax.vjp(layer_r, h, lp, a_tm, a_cm, a_wkv)
            cts = (ct, jnp.zeros_like(outs[1]), jnp.zeros_like(outs[2]),
                   jnp.zeros_like(outs[3]))
            return vjp(cts)

        lowered = jax.jit(f, in_shardings=(lp_shard, hs, hs) + st_sh).lower(
            lp_shape, h_spec, h_spec, st_tm, st_cm, wkv)
    else:
        def f(lp, h, a_tm, a_cm, a_wkv):
            return layer(h, lp, a_tm, a_cm, a_wkv)
        lowered = jax.jit(f, in_shardings=(lp_shard, hs) + st_sh).lower(
            lp_shape, h_spec, st_tm, st_cm, wkv)
    return _measure(lowered)


def _rwkv_layer_decode(model, mesh, b):
    cfg, impl = model.cfg, model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    st_tm, st_cm, wkv = _rwkv_states(cfg, impl, b, "step")
    st_sh = _rwkv_state_shardings(mesh, b, impl)
    h_spec = jax.ShapeDtypeStruct((b, cfg.d_model), jnp.bfloat16)
    sb = shd._ax(mesh, b, "data")
    hs = _ns(mesh, P(sb, "model"))
    from repro.models.rwkv6 import wkv_step

    def f(lp, hh, a_tm, a_cm, a_wkv):
        a = LYR.apply_norm(lp["ln1"], hh, "layernorm")
        r, k, v, g, logw = impl._tm_proj(lp["time_mix"], a,
                                         a_tm.astype(a.dtype))
        o, n_wkv = wkv_step(r.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), logw,
                            lp["time_mix"]["u"].astype(jnp.float32), a_wkv)
        o = LYR.group_norm_heads(o.astype(a.dtype), lp["time_mix"]["ln_x"])
        o = (o.reshape(*hh.shape[:-1], -1) * g) @ lp["time_mix"]["wo"].astype(a.dtype)
        hh = hh + o
        c = LYR.apply_norm(lp["ln2"], hh, "layernorm")
        dx = a_cm.astype(c.dtype) - c
        xk = c + dx * lp["channel_mix"]["mu_k"].astype(c.dtype)
        xr = c + dx * lp["channel_mix"]["mu_r"].astype(c.dtype)
        kk = jnp.square(jax.nn.relu(xk @ lp["channel_mix"]["wk"].astype(c.dtype)))
        o2 = jax.nn.sigmoid(xr @ lp["channel_mix"]["wr"].astype(c.dtype)) * (
            kk @ lp["channel_mix"]["wv"].astype(c.dtype))
        return hh + o2, a.astype(jnp.bfloat16), c.astype(jnp.bfloat16), n_wkv

    lowered = jax.jit(f, in_shardings=(lp_shard, hs) + st_sh).lower(
        lp_shape, h_spec, st_tm, st_cm, wkv)
    return _measure(lowered)


# ---------------------------------------------------------------------------
# zamba (mamba2 hybrid) probes
# ---------------------------------------------------------------------------


def _zamba_components(model, mesh, b, s, kind):
    """Returns (mamba_cost, attn_cost) for seq (train fwd basis) or
    decode."""
    from repro.models import mamba2 as M
    cfg, impl = model.cfg, model.impl
    stacked = jax.eval_shape(
        lambda: jax.eval_shape(impl.init, jax.random.PRNGKey(0))["layers"])
    lp_shape, lp_shard = _layer_param_probe(cfg, mesh, model, stacked)
    d_inner, n_heads, conv_dim = M.mamba2_dims(cfg)
    sb = shd._ax(mesh, b, "data")
    conv_spec = jax.ShapeDtypeStruct((b, M.D_CONV - 1, conv_dim), jnp.float32)
    ssm_spec = jax.ShapeDtypeStruct((b, n_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state), jnp.float32)
    conv_sh = _ns(mesh, P(sb, None, "model"))
    ssm_sh = _ns(mesh, P(sb, None, None, None))
    # shared attn params
    full_shape = jax.eval_shape(impl.init, jax.random.PRNGKey(0))
    sp_shape = full_shape["shared_attn"]
    sp_shard = jax.tree.map(
        lambda spc: _ns(mesh, spc),
        shd.param_specs(mesh, {"shared_attn": sp_shape}))["shared_attn"]

    if kind in ("train", "prefill"):
        h_spec = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        hs = _h_sharding(mesh, b)
        # The mamba layer's cost is exactly linear in S (identical chunks,
        # no cross-chunk term): probe ONE chunk and scale by S/chunk to
        # keep the unrolled-vjp module small.
        s_probe = min(s, impl.chunk)
        mamba_scale = s / s_probe
        hm_spec = jax.ShapeDtypeStruct((b, s_probe, cfg.d_model),
                                       jnp.bfloat16)

        def mamba_f(lp, h, cs, ss):
            a = LYR.apply_norm(lp["norm"], h, "rmsnorm")
            o, ncs, nss = M.apply_mamba2_seq(lp["mamba"], a, cfg, cs, ss,
                                             chunk=impl.chunk, unroll=True)
            return h + o, ncs, nss

        positions = jnp.arange(s, dtype=jnp.int32)[None, :]

        def attn_f(sp, h):
            return impl._shared_attn_seq(sp, h, positions.repeat(b, 0),
                                         jnp.bfloat16)

        if kind == "train":
            mamba_r = jax.checkpoint(mamba_f)

            def mg(lp, h, ct, cs, ss):
                outs, vjp = jax.vjp(mamba_r, lp, h, cs, ss)
                return vjp((ct, jnp.zeros_like(outs[1]),
                            jnp.zeros_like(outs[2])))
            lowered_m = jax.jit(mg, in_shardings=(lp_shard, hs, hs, conv_sh,
                                                  ssm_sh)).lower(
                lp_shape, hm_spec, hm_spec, conv_spec, ssm_spec)
            attn_r = jax.checkpoint(attn_f)

            def ag(sp, h, ct):
                (hh, (kc, vc)), vjp = jax.vjp(attn_r, sp, h)
                return vjp((ct, (jnp.zeros_like(kc), jnp.zeros_like(vc))))
            lowered_a = jax.jit(ag, in_shardings=(sp_shard, hs, hs)).lower(
                sp_shape, h_spec, h_spec)
            mc = _measure(lowered_m)
            mc = {k: v * mamba_scale for k, v in mc.items()}
            return mc, _measure(lowered_a)
        else:
            lowered_m = jax.jit(mamba_f,
                                in_shardings=(lp_shard, hs, conv_sh, ssm_sh)
                                ).lower(lp_shape, hm_spec, conv_spec,
                                        ssm_spec)
            lowered_a = jax.jit(attn_f, in_shardings=(sp_shard, hs)).lower(
                sp_shape, h_spec)
        mc = _measure(lowered_m)
        mc = {k: v * mamba_scale for k, v in mc.items()}
        return mc, _measure(lowered_a)

    # decode
    h_spec = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
    hs = _h_sharding(mesh, b)
    kv_spec = jax.ShapeDtypeStruct((b, cfg.n_kv_heads, s, cfg.hd),
                                   jnp.bfloat16)
    kvs = _kv_shard(mesh, b, s)

    def mamba_step(lp, h, cs, ss):
        a = LYR.apply_norm(lp["norm"], h, "rmsnorm")
        o, ncs, nss = M.apply_mamba2_step(lp["mamba"], a[:, 0], cfg, cs, ss)
        return h + o[:, None, :], ncs, nss

    lowered_m = jax.jit(mamba_step,
                        in_shardings=(lp_shard, hs, conv_sh, ssm_sh)).lower(
        lp_shape, h_spec, conv_spec, ssm_spec)

    def attn_step(sp, h, kc, vc, index):
        return impl._shared_attn_step(sp, h, kc, vc, index)

    lowered_a = jax.jit(attn_step,
                        in_shardings=(sp_shard, hs, kvs, kvs, _ns(mesh, P())),
                        donate_argnums=(2, 3)).lower(
        sp_shape, h_spec, kv_spec, kv_spec,
        jax.ShapeDtypeStruct((), jnp.int32))
    return _measure(lowered_m), _measure(lowered_a)


# ---------------------------------------------------------------------------
# optimizer probe
# ---------------------------------------------------------------------------


def _opt_probe(model, mesh):
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = jax.tree.map(lambda sp: _ns(mesh, sp),
                          shd.param_specs(mesh, params_shape))
    init_fn, upd_fn = adamw(lr=warmup_cosine(3e-4, 100, 10_000))
    opt_shape = jax.eval_shape(init_fn, params_shape)
    oshard = type(opt_shape)(step=_ns(mesh, P()), m=pshard, v=pshard)

    def step(g, o, p):
        g, gn = clip_by_global_norm(g, 1.0)
        p, o = upd_fn(g, o, p)
        return p, o, gn

    g_shape = jax.tree.map(
        lambda sds: jax.ShapeDtypeStruct(sds.shape, jnp.float32),
        params_shape)
    lowered = jax.jit(step, in_shardings=(pshard, oshard, pshard),
                      out_shardings=(pshard, oshard, _ns(mesh, P()))).lower(
        g_shape, opt_shape, params_shape)
    return _measure(lowered)


# ---------------------------------------------------------------------------
# main entry
# ---------------------------------------------------------------------------


def probe_cell(arch_id: str, shape_name: str, mesh, mesh_name: str,
               opt_level: int = 0) -> dict:
    cfg = get_arch(arch_id)
    shape = SHAPES[shape_name]
    kv_quant = ("int8" if (opt_level >= 2 and shape.kind == "decode"
                           and cfg.block_type == "transformer") else "none")
    moe_impl = ("shardmap" if (opt_level >= 2 and cfg.is_moe
                               and shape.kind == "train") else "dense")
    model = get_model(cfg, compute_dtype=jnp.bfloat16, remat="full",
                      unroll_inner=True,
                      **({"kv_quant": kv_quant, "moe_impl": moe_impl}
                         if cfg.block_type == "transformer" else {}))
    L = cfg.n_layers
    fam = cfg.block_type

    if shape.kind == "train":
        from repro.launch.dryrun import grad_accum_for
        ga = grad_accum_for(cfg)
        b = shape.global_batch // ga
        mb_specs = jax.tree.map(
            lambda sds: jax.ShapeDtypeStruct((b,) + sds.shape[1:], sds.dtype),
            model.input_specs(shape))
        tail = _tfm_tail_train(model, mesh, mb_specs)
        opt = _opt_probe(model, mesh)
        if fam == "transformer":
            layer = _tfm_layer_train(model, mesh, b, shape.seq_len,
                                     opt=opt_level)
            per_step = _sum(layer, tail, weights=[L, 1.0])
        elif fam == "rwkv6":
            layer = _rwkv_layer(model, mesh, b, shape.seq_len, train=True)
            per_step = _sum(layer, tail, weights=[L, 1.0])
        else:
            mamba, attn = _zamba_components(model, mesh, b, shape.seq_len,
                                            "train")
            n_attn = len(model.impl.groups)
            per_step = _sum(mamba, attn, tail, weights=[L, n_attn, 1.0])
        total = _sum(per_step, opt, weights=[ga, 1.0])
        total["components"] = {"tail": tail, "opt": opt, "ga": ga}
        return total

    b, s = shape.global_batch, shape.seq_len
    tail = _serve_tail(model, mesh, shape, shape.kind, opt=opt_level)
    if fam == "transformer":
        if shape.kind == "prefill":
            layer = _tfm_layer_prefill(model, mesh, b, s)
        else:
            layer = _tfm_layer_decode(model, mesh, b, s, opt=opt_level)
        total = _sum(layer, tail, weights=[L, 1.0])
    elif fam == "rwkv6":
        if shape.kind == "prefill":
            layer = _rwkv_layer(model, mesh, b, s, train=False)
        else:
            layer = _rwkv_layer_decode(model, mesh, b)
        total = _sum(layer, tail, weights=[L, 1.0])
    else:
        mamba, attn = _zamba_components(model, mesh, b, s, shape.kind)
        n_attn = len(model.impl.groups)
        total = _sum(mamba, attn, tail, weights=[L, n_attn, 1.0])
    total["components"] = {"tail": tail}
    return total
