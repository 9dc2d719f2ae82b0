"""hybrid_decode_mfu: model operations of the tokens decoded in the
traced window by a hybrid Mamba-2 / attention model
(bench/kernels/hybrid_step.py) over the device time of the
``decode_horizon_step`` program times the chip's bf16 peak, in %."""
import os

from bench import harness, peaks, stats

_K = harness.load_module(os.path.join(harness.BENCH, "kernels",
                                      "hybrid_step.py"))


def compute(rec, tr):
    hz = stats.traced(rec, "horizons")
    t = tr["modules"].get("decode_horizon_step", 0.0)
    if not hz or not t:
        return None
    ops = sum(_K.horizon_ops(rec["config"], rows, rec["horizon"])
              for _, _, rows in hz)
    return 100.0 * ops / (t * peaks.peaks(tr["device_kind"])["bf16_flops"])
