"""Find a cell's knee: its traffic at several fixed rates (needs a TPU).

  python bench/sweep.py --workload <cell> --rates 2,3,4 [--seconds 30]
      [--seed 7] [--drain 5]

Sets the serving cell up once and offers the cell's open-loop mix at
each rate in turn.  Per rate it prints the completed requests per
second, the p50/p90 latencies of those due in the window, and how
many were still queued or unfinished when the window closed: past the
knee the queue grows through the window.
The benchmark fixes a rate below the knee in the mix file; its own runs
never search.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import numpy as np                                          # noqa: E402

from bench import harness                                   # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pct(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else None


def serve_point(drv, S, cell, rate, seed, seconds, drain):
    c = cell.cell
    mix = dict(cell.mix, arrivals={"kind": "poisson", "rate_per_s": rate})
    S.reset()
    rec = drv.measure(S, mix, seed, seconds, lead=c["lead_s"], drain=drain,
                      log=log)
    ws, we = rec["window"]
    got = [r for r in rec["requests"] if r["counted"]]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in got if r["first"]]
    tpot = [(r["done"] - r["first"]) * 1e3 / (r["n_out"] - 1)
            for r in got if r["done"]]
    done_in = sum(1 for r in rec["requests"]
                  if r["done"] and ws <= r["done"] < we)
    return {"rate": rate, "due": len(got), "finished": len(tpot),
            "completed_per_s": done_in / (we - ws),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
            "tpot_p50_ms": pct(tpot, 50), "tpot_p90_ms": pct(tpot, 90),
            "waiting_at_end": rec["waiting_at_end"],
            "unfinished": rec["unanswered"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        log("sweep: needs a TPU")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drv = harness.driver_module(cell)
    S = drv.Served(cell, args.seed, log)
    for rate in (float(r) for r in args.rates.split(",")):
        row = serve_point(drv, S, cell, rate, args.seed, args.seconds,
                          args.drain)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
