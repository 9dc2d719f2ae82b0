"""Readings that the limit of the hybrid cell's ``correct`` is set from
(needs a TPU).

  python bench/calibrate_hybrid.py --workload granite-h-micro-reasoning32 \
      --seeds 1,2,... [--control-seeds 1,2] [--seconds 51] [--out FILE]

As ``bench/calibrate.py`` does for the dense cell, in one process and
set up once: each seed makes its own weights (from the seed, as
``bench/drivers/serve_hybrid.py`` makes them), window and check, and
reads the compared numbers of the program on the requests that window
sampled, and what says whether they can tell a fault: the share of
distinct tokens among the served ones, the share that repeat the token
before them, and the median lead of the reference's best logit over its
second at the served positions. For the check the pages and state slots
are dropped and the reference reads the same weights, whose values are
what the reference would make from the seed; the next seed's window
starts from a new store. On the control seeds it also reads the int8
control (``bench/ref_hybrid.py``) on the same requests. Prints one JSON
line per seed and appends it to ``--out``.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from bench import harness, ref_hybrid, stats                # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


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
        log("calibrate_hybrid: needs a TPU")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drv = harness.driver_module(cell)
    c = cell.cell
    S = drv.Served(cell, seeds[0], log)
    for seed in seeds:
        if seed != seeds[0]:
            S.server.params = None
            gc.collect()
            S.server.params = S.make_weights(seed)
        S.reset()
        rec = drv.measure(S, cell.mix, seed, args.seconds, lead=c["lead_s"],
                          drain=c["drain_s"], log=log)
        pairs = drv.sample(rec.pop("finished"), c["check"]["requests"], seed)
        srv = S.server
        srv.store = srv.table = None
        gc.collect()
        got = drv.check(cell, pairs, S.wseed, S.dtype, log,
                        control=seed in ctrl, params=srv.params)
        served = [o for _, o in pairs]
        lead = np.concatenate([ref_hybrid.margins(cell.config, srv.params,
                                                  p, o) for p, o in pairs])
        prev = [np.concatenate([p[-1:], o[:-1]]) for p, o in pairs]
        srv.store = srv._new_store()
        srv.table = srv._new_table()
        ws, we = rec["window"]
        row = {"seed": seed, "requests": len(pairs),
               "tokens": got["checked_tokens"],
               "served_gap_max": got["served_gap_max"],
               "distinct_share": float(np.mean([len(set(o.tolist())) / len(o)
                                                for o in served])),
               "repeat_share": float(np.mean(np.concatenate(
                   [o == q for o, q in zip(served, prev)]))),
               "lead_p50": float(np.median(lead)),
               "requests_unanswered": rec["unanswered"],
               "output_tok_s": stats.prorated(rec["steps"], ws, we) /
               (we - ws)}
        if "control_int8_reference" in got:
            row["control_int8_reference"] = got["control_int8_reference"]
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
