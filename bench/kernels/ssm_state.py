"""Operations and bytes of the ``ssm_state_update`` kernel (one Mamba-2
layer's decode state update), from the rows whose state it updates.

Per row and layer the kernel reads the row's state slot and writes it
back (``H * dh * ds`` float32 each way), reads ``x`` (``H * dh``),
``dt`` and ``exp(dt * A)`` (``H`` each), ``B`` and ``C`` (``ds`` each),
and writes ``y`` (``H * dh``), all float32.  Per state element it
multiplies by the decay, multiplies ``dt * x`` by ``B``, adds, and
multiply-adds into ``y``: 5 operations; the ``D * x`` skip adds 2 per
``H * dh``.  Only rows that update a slot count: padding rows and rows
past their budget are skipped by the kernel and cost nothing here.
"""
from __future__ import annotations

F32 = 4


def row_cost(*, n_heads: int, head_dim: int, d_state: int):
    """(operations, bytes) of one row's update in one layer."""
    state = n_heads * head_dim * d_state
    ops = 5 * state + 2 * n_heads * head_dim
    nbytes = F32 * (2 * state + 2 * n_heads * head_dim + 2 * n_heads +
                    2 * d_state)
    return ops, nbytes


def cost(state_rows: int, n_layers: int, **dims):
    """(operations, bytes) of every kernel call over ``state_rows`` row
    updates (live rows x decode steps) in each of ``n_layers`` layers."""
    ops, nbytes = row_cost(**dims)
    n = state_rows * n_layers
    return ops * n, nbytes * n


def dims(cfg: dict) -> dict:
    """The kernel's sizes, from a configuration file."""
    return dict(n_heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
                d_state=cfg["mamba_d_state"])
