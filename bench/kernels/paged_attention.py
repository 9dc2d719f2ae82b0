"""Operations and bytes of the ``paged_attention`` kernel, from valid lengths.

One call attends each row's query heads over that row's valid context.
Only the work the rows need is counted — the valid tokens, never the
pow2-padded batch or page-table width, nor the unused tail of a page —
so a share of the roofline cannot pass 100% and a change that cuts
padding is credited.

  operations = 4 * sum(lengths) * n_heads * head_dim   (q.k and p.v)
  bytes      = sum(lengths) * 2 * n_kv_heads * head_dim * kv_bytes
               + rows * 2 * n_heads * head_dim * act_bytes  (q in, o out)
"""
from __future__ import annotations


def cost(lengths, *, n_heads: int, n_kv_heads: int, head_dim: int,
         kv_bytes: int = 2, act_bytes: int = 2):
    """(operations, bytes) of one call over rows of context ``lengths``
    (rows of length 0 are padding and cost nothing)."""
    tok = sum(int(n) for n in lengths if n > 0)
    rows = sum(1 for n in lengths if n > 0)
    ops = 4 * tok * n_heads * head_dim
    nbytes = (tok * 2 * n_kv_heads * head_dim * kv_bytes +
              rows * 2 * n_heads * head_dim * act_bytes)
    return ops, nbytes


def horizon_cost(rows, horizon: int, n_layers: int, **dims):
    """(operations, bytes) of every kernel call in one decode horizon.
    ``rows``: per sequence (committed length before the horizon, tokens
    it emitted).  At step ``i`` a sequence that emits its ``i+1``-th
    token attends over ``length + i + 1`` positions, once per layer."""
    ops = nbytes = 0
    for i in range(horizon):
        lens = [n + i + 1 for n, e in rows if e > i]
        o, b = cost(lens, **dims)
        ops += o * n_layers
        nbytes += b * n_layers
    return ops, nbytes
