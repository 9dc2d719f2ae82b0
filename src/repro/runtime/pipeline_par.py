"""Pipeline parallelism over the ``pod`` axis (GPipe schedule).

The paper's storage pool runs pipeline-parallel inference across
DockerSSDs (Fig 8b); at training scale the analogous structure maps the
layer stack onto the ``pod`` mesh axis: stage *i* holds layers
[i*L/S, (i+1)*L/S), microbatches stream through stages via
``lax.ppermute``, and autodiff through the permutes yields the reverse
pipeline for the backward pass.

Implementation: ``shard_map`` over the full mesh; within it the layer
stack's leading dim is sharded over ``pod`` (each stage owns its slice),
batch over ``data``, weights additionally sharded over ``model`` exactly
as in the non-pipelined path (GSPMD handles the intra-stage TP because
we re-enter jit-style tracing via the collectives-only schedule below).

This is the scale path for models whose per-layer weights exceed what
FSDP alone can hold per chip; demonstrated at test scale in
``tests/test_pipeline_par.py``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def make_pipeline_loss(model, mesh, *, n_microbatches: int,
                       stage_axis: str = "pod"):
    """Returns loss_fn(params, batch) running the transformer backbone as
    a GPipe pipeline over ``stage_axis``.

    Constraints: transformer-family model; n_layers % n_stages == 0;
    global batch % (n_microbatches * data_axis) == 0.  Embedding + loss
    tail execute on every stage (they are cheap and replicated over the
    stage axis), which keeps the schedule simple: only hidden states
    travel between stages.
    """
    cfg = model.cfg
    impl = model.impl
    n_stages = mesh.shape[stage_axis]
    assert cfg.n_layers % n_stages == 0
    per_stage = cfg.n_layers // n_stages
    data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]

        # specs: layer stack sharded over the stage axis; the rest of the
        # params replicated over it (embed/head participate everywhere)
        def stage_spec(path, leaf):
            keys = [getattr(p_, "key", str(p_)) for p_ in path]
            if keys and keys[0] == "layers":
                return P(stage_axis)
            return P()

        pspecs = jax.tree_util.tree_map_with_path(stage_spec, params)
        bspec = P(data_axes[0] if data_axes else None, None)

        def staged(params_local, tok_local, lab_local):
            stage = lax.axis_index(stage_axis)
            layers_local = params_local["layers"]      # [per_stage, ...]

            def run_stage(h):
                def body(hh, lp):
                    hh, _ = impl._layer(hh, lp, None if False else
                                        jnp.arange(hh.shape[1],
                                                   dtype=jnp.int32)[None, :]
                                        .repeat(hh.shape[0], 0))
                    return hh, None
                h, _ = lax.scan(body, h, layers_local)
                return h

            def embed(tok_mb):
                return impl._inputs_to_h(params_local, {"tokens": tok_mb})

            def tail_loss(h, lab_mb):
                from repro.models import layers as L
                hh = L.apply_norm(params_local["final_norm"], h, cfg.norm)
                logits = L.unembed(params_local["embed"],
                                   params_local.get("lm_head"), hh,
                                   cfg.tie_embeddings)
                mask = (lab_mb != -1).astype(jnp.float32)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(
                    logits, lab_mb[..., None].clip(0), axis=-1)[..., 0]
                # (1,)-shaped, not scalar: old-jax shard_map partial
                # eval mis-specs scalar device-varying residuals
                return (jnp.sum((lse - gold) * mask)[None],
                        jnp.sum(mask)[None])

            # GPipe: n_microbatches + n_stages - 1 ticks.  At each tick a
            # stage processes one microbatch-slot and passes it downstream.
            # shapes are LOCAL here (inside shard_map)
            b_loc, seq = tok_local.shape
            assert b_loc % n_microbatches == 0, (
                f"local batch {b_loc} must divide into "
                f"{n_microbatches} microbatches")
            mb = b_loc // n_microbatches
            toks = tok_local.reshape(n_microbatches, mb, seq)
            labs = lab_local.reshape(n_microbatches, mb, seq)
            buf = jnp.zeros((mb, seq, cfg.d_model), jnp.float32)
            nll = jnp.zeros((1,))
            cnt = jnp.zeros((1,))
            n_ticks = n_microbatches + n_stages - 1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                buf, nll, cnt = carry
                # stage 0 injects microbatch t (if valid)
                mb_idx = jnp.clip(t, 0, n_microbatches - 1)
                fresh = embed(toks[mb_idx]).astype(jnp.float32)
                h_in = jnp.where(jnp.equal(stage, 0)[None, None, None],
                                 fresh, buf)
                h_out = run_stage(h_in.astype(impl.compute_dtype)).astype(
                    jnp.float32)
                # last stage computes the loss for the microbatch that
                # entered n_stages-1 ticks ago
                out_idx = jnp.clip(t - (n_stages - 1), 0,
                                   n_microbatches - 1)
                l, c = tail_loss(h_out.astype(impl.compute_dtype),
                                 labs[out_idx])
                valid = ((t - (n_stages - 1) >= 0) &
                         (t - (n_stages - 1) < n_microbatches) &
                         (stage == n_stages - 1))
                nll = nll + jnp.where(valid, l, 0.0)
                cnt = cnt + jnp.where(valid, c, 0.0)
                # hand the activation to the next stage
                buf = lax.ppermute(h_out, stage_axis, perm)
                return (buf, nll, cnt), None

            (buf, nll, cnt), _ = lax.scan(tick, (buf, nll, cnt),
                                          jnp.arange(n_ticks))
            # total loss lives on the last stage; share it with everyone
            nll = lax.psum(nll, stage_axis)
            cnt = lax.psum(cnt, stage_axis)
            if data_axes:
                nll = lax.psum(nll, data_axes)
                cnt = lax.psum(cnt, data_axes)
            return (nll / jnp.maximum(cnt, 1.0))[0]

        fn = jax.shard_map(staged, mesh=mesh,
                           in_specs=(pspecs, bspec, bspec),
                           out_specs=P(), check_vma=False)
        return fn(params, tokens, labels)

    return loss_fn
