"""decode_step_ms: device time of the ``decode_horizon_step`` program in
the traced window per decode step (horizons x H)."""
from bench import stats


def compute(rec, tr):
    n = len(stats.traced(rec, "horizons")) * rec["horizon"]
    t = tr["modules"].get("decode_horizon_step", 0.0)
    return t * 1e3 / n if n and t else None
