"""attn_block_fill: share of the paged-attention kernel's block grid
that holds KV, in % — over the window's ``server.horizon`` spans, the
blocks their rows' pages fill (``blocks``) over ``block_grid``, the
``bucket_rows`` x blocks of ``pages_per_block`` pages of the horizon's
table.  The kernel steps only through the blocks that hold KV (and
once through an empty row), so this is about the share of that full
grid its steps still cover.  A horizon whose planning failed holds no
grid counts; it is left out.  None where the program keeps no span log
(``repro.runtime.tracing``), where its horizons count no blocks (a
program whose kernel steps page by page), or where the log no longer
holds the window whole."""
from bench import spans


def compute(rec, tr):
    recs = spans.records(rec)
    if not recs:
        return None
    hz = [r.counts for r in recs
          if r.name == "server.horizon" and "blocks" in r.counts]
    grid = sum(c["block_grid"] for c in hz)
    if not grid:
        return None
    return 100.0 * sum(c["blocks"] for c in hz) / grid
