"""Readings that the limits of ``correct`` are set from (needs a TPU).

  python bench/calibrate.py --workload <cell> --seeds 1,2,... \
      [--control-seeds 1,2,3] [--seconds 51] [--out FILE]

For each seed it makes one whole run of the cell, as ``bench/run.py``
does (set-up, window, check), and reads the compared numbers of the
program on the requests that run sampled.  On the control seeds it also
reads the control on the same requests: the reference in int8 (weights
and activations fake-quantized, ``bench/ref_lm.py``), as the gap of the
token it puts first under the float32 reference.

Prints one JSON line per seed and writes them to ``--out``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from bench import harness, ref_lm, weights                 # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    drv = harness.driver_module(cell)
    rec = drv.run(cell, seed=seed, seconds=seconds, trace=False, log=log)
    pairs = rec["checked_pairs"]
    row = {"seed": seed, "requests": len(pairs),
           "tokens": rec["checked_tokens"],
           **{k: v["value"] for k, v in rec["checks"].items()}}
    if control:
        dtype = drv.DTYPES[cell.cell["server"]["dtype"]]
        params = weights.make(cell.config, rec["wseed"], dtype)
        row["control_int8_reference"] = float(max(
            ref_lm.control_gaps(cell.config, params, p, o).max()
            for p, o in pairs))
        del params
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    cell = harness.find_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        log("calibrate: needs a TPU")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        line = json.dumps(readings(cell, seed, args.seconds, seed in ctrl))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
