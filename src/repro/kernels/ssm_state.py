"""Mamba-2 decode state update — Pallas TPU kernel.

One decode step of one Mamba-2 layer for a batch of rows, on state
slots kept beside the KV pages (``core.kv_tier.PageStore``): the whole
stacked state ``[n_layers, n_slots, H, dh, ds]`` f32 stays in HBM, and
a row's slot and the layer come from scalar-prefetch operands, as the
paged kernel takes its pages.  Per row and head:

    h' = exp(dt * A) * h + dt * x (outer) B        [dh, ds]
    y  = h' . C + D * x                            [dh]

The grid steps over rows; one step DMAs the row's slot of the layer
(all heads, ``H * dh * ds`` f32) into VMEM, updates it head by head in a
loop and writes it back in place (``input_output_aliases``), so the
state is never copied or sliced outside the kernel.  A row whose slot
is negative (padding, or a row past its token budget) is skipped: its
block index names the store's last slot, which no sequence holds, and
nothing is computed.

``x`` and ``y`` travel as ``[rows, H, dh]`` (a head is a row of lanes);
the head's ``x`` becomes the column the outer product needs, and ``y``
the row it is stored as, through a masked reduction with the identity
(exact in f32).  The per-row, per-head scalars ``exp(dt * A)`` and
``dt``, and ``D``, sit in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, li_ref, dec_ref, dt_ref, d_ref, x_ref, b_ref, c_ref,
            st_ref, y_ref, o_ref, *, n_heads: int):
    r = pl.program_id(0)

    @pl.when(slot_ref[r] >= 0)
    def _update():
        dh = x_ref.shape[-1]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 0) ==
               jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 1)
               ).astype(jnp.float32)
        bm = b_ref[0]                                   # [1, ds]
        cm = c_ref[0]

        def head(h, carry):
            i = r * n_heads + h
            x_row = x_ref[0, pl.ds(h, 1), :]            # [1, dh]
            x = jnp.sum(eye * x_row, axis=1, keepdims=True)   # [dh, 1]
            new = dec_ref[i] * st_ref[h] + (dt_ref[i] * x) * bm
            o_ref[h] = new
            y = jnp.sum(new * cm, axis=1, keepdims=True) + d_ref[h] * x
            y_ref[0, pl.ds(h, 1), :] = jnp.sum(eye * y, axis=0,
                                               keepdims=True)
            return carry

        jax.lax.fori_loop(0, n_heads, head, 0)

    @pl.when(slot_ref[r] < 0)
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_state_update(state, x, dt, A, B, C, D, slots, layer, *,
                     interpret: bool = False):
    """One token's state update of layer ``layer`` for every row.

    state: [n_layers, n_slots, H, dh, ds] f32, its last slot held by no
    sequence; x: [R, H, dh]; dt: [R, H] (after softplus); A: [H]
    (negative: ``-exp(A_log)``); B, C: [R, ds]; D: [H]; slots: [R]
    int32, negative to skip a row; layer: int32 scalar.  Returns
    (y [R, H, dh] f32, state) — the state updated in place."""
    n_layers, n_slots, h, dh, ds = state.shape
    rows = x.shape[0]
    f32 = jnp.float32
    dt = dt.astype(f32)
    dec = jnp.exp(dt * A.astype(f32)[None])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def row_spec(*shape):
        return pl.BlockSpec((1,) + shape, lambda r, s, li: (r, 0, 0))

    state_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, h, dh, ds),
        lambda r, s, li: (li[0], jnp.where(s[r] >= 0, s[r], n_slots - 1),
                          0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows,),
        in_specs=[smem, smem, smem, row_spec(h, dh), row_spec(1, ds),
                  row_spec(1, ds), state_spec],
        out_specs=[row_spec(h, dh), state_spec],
    )
    y, state = pl.pallas_call(
        functools.partial(_kernel, n_heads=h),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, h, dh), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two scalar-prefetch ones: the state is 8th
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_state_update",
    )(slots.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      dec.reshape(-1), dt.reshape(-1), D.astype(f32), x.astype(f32),
      B.astype(f32).reshape(rows, 1, ds), C.astype(f32).reshape(rows, 1, ds),
      state)
    return y, state
