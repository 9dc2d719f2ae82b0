"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` (see ``bench/harness.py``).
Set-up (weights or data from the seed, every program the cell's traffic
can reach, a warm request) is timed as ``setup_s``; then the cell's
driver offers its traffic for ``--seconds`` and checks what the timed
path produced against a plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` records a device trace of part
of the window and reports the per-layer metrics.

Earlier lines (stderr) report the device, set-up, programs warmed,
compiles inside the window, how late the generator ran, and at the end
each compared number beside its limit.  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``busy_s``/``window_s`` and a
``breakdown``), and the compared numbers under ``checks``.

Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from bench import harness                                   # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devs)} {devs[0].platform} device(s)")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device: {len(devs)} x {devs[0].device_kind}; jax {jax.__version__};"
        f" compile cache {cache}")

    drv = harness.driver_module(cell)
    rec = drv.run(cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), log=log)
    tr = rec.get("trace")
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = harness.compute_metrics(cell, kind, rec, tr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(rec["memory_peak_bytes"])}
    breakdown = None
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        breakdown = tr["breakdown"]
    out = harness.result_line(rec, metrics, device, breakdown)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
