"""output_tok_s: output tokens emitted in the window (first tokens
included; a scheduler step that straddles an edge counts by the share
of it inside), divided by the window."""
from bench import stats


def compute(rec, tr):
    ws, we = rec["window"]
    return stats.prorated(rec["steps"], ws, we) / (we - ws)
