"""ssm_state_roofline: the least time the ``ssm_state_update`` calls of
the traced decode horizons need (larger of operations over the bf16
peak and bytes over HBM bandwidth, bench/kernels/ssm_state.py) over
their summed kernel time, in %.  The row updates are the ``state_rows``
counts of the ``server.horizon`` spans inside the traced window (live
rows x decode steps), once per Mamba layer; the kernel time is the
trace's ``decode_horizon_step/ssm_state_update`` operation time.  None
where the program keeps no such counter or kernel (the commit before
them), or the kernel is not among the trace's ten costliest operations.
"""
import os

from bench import harness, peaks, spans

_K = harness.load_module(os.path.join(harness.BENCH, "kernels",
                                      "ssm_state.py"))
OP = "decode_horizon_step/ssm_state_update"


def compute(rec, tr):
    recs = spans.records(rec)
    t = dict(tr["breakdown"]["device_ops"]).get(OP, 0.0)
    if not recs or not t:
        return None
    t0, t1 = rec["trace_window"]
    rows = sum(r.counts["state_rows"] for r in recs
               if r.name == "server.horizon" and "state_rows" in r.counts
               and r.start >= t0 and r.end <= t1)
    if not rows:
        return None
    cfg = rec["config"]
    n_layers = sum(k == "mamba" for k in cfg["layer_types"])
    ops, nbytes = _K.cost(rows, n_layers, **_K.dims(cfg))
    pk = peaks.peaks(tr["device_kind"])
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
