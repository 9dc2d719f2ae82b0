"""paged_attn_roofline: the least time the ``paged_attention`` calls of
the traced decode horizons need (larger of operations over the bf16
peak and valid KV bytes over HBM bandwidth, bench/kernels/
paged_attention.py) over their summed kernel time, in %.  Calls inside
the prefill program are not counted here."""
import os

from bench import harness, peaks, stats

_K = harness.load_module(os.path.join(harness.BENCH, "kernels",
                                      "paged_attention.py"))


def compute(rec, tr):
    hz = stats.traced(rec, "horizons")
    t = tr["kernels"].get("paged_attention", {}).get(
        "decode_horizon_step", 0.0)
    if not hz or not t:
        return None
    cfg = rec["config"]
    dims = dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"])
    ops = nbytes = 0
    for _, _, rows in hz:
        o, b = _K.horizon_cost(rows, rec["horizon"],
                               cfg["num_hidden_layers"], **dims)
        ops += o
        nbytes += b
    pk = peaks.peaks(tr["device_kind"])
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
