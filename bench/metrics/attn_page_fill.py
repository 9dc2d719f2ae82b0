"""attn_page_fill: share of the paged-attention grid that holds KV, in
% — over the window's ``server.horizon`` spans, the pages their rows
reserve over ``bucket_rows`` x ``table_width``, the pow2-padded grid
every kernel call of the horizon steps through.  A horizon whose
planning failed ran no kernel and holds no grid counts; it is left out.
None where the program keeps no span log (``repro.runtime.tracing``) or
the log no longer holds the window whole."""
from bench import spans


def compute(rec, tr):
    recs = spans.records(rec)
    if not recs:
        return None
    hz = [r.counts for r in recs
          if r.name == "server.horizon" and "pages" in r.counts]
    grid = sum(c["bucket_rows"] * c["table_width"] for c in hz)
    if not grid:
        return None
    return 100.0 * sum(c["pages"] for c in hz) / grid
