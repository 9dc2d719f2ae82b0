"""Finds a cell's pieces by name and turns a run's record into the result.

Everything about one cell lives in files of its own, found by the names
in ``BENCHMARK.json``:

  * ``bench/configs/<config>.json`` — the configuration as it is run
    (named by the ``file`` of its ``configs`` entry);
  * ``bench/cells/<cell>.json`` — driver, server and scheduler
    settings, the check's sample size and limits;
  * ``bench/mixes/<traffic>.json`` — the traffic mix, read by
    ``bench/gen.py``;
  * ``bench/drivers/<driver>.py`` — the timed loop of one kind of entry;
  * ``bench/metrics/<metric>.py`` — ``compute(rec, tr)`` of one metric,
    returning a number or None when the run has nothing to read.

So a new cell, mix, configuration or metric is new files and new
entries in ``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (names may hold '.' and '-')."""
    name = "bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    cell: dict
    mix: dict
    metrics: dict          # {"end_to_end": [entries], "per_layer": [...]}
    root: str

    @property
    def driver(self) -> str:
        return self.cell["driver"]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s BENCHMARK.json, with its files."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, conf["file"])),
        cell=load_json(os.path.join(bench, "cells", name + ".json")),
        mix=load_json(os.path.join(bench, "mixes", w["traffic"] + ".json")),
        metrics={kind: [m for m in spec[kind] if _applies(m, name)]
                 for kind in ("end_to_end", "per_layer")},
        root=root)


def driver_module(cell: Cell):
    return load_module(os.path.join(cell.root, "bench", "drivers",
                                    cell.driver + ".py"))


def compute_metrics(cell: Cell, kind: str, rec: dict,
                    tr: Optional[dict]) -> dict:
    """{name: {"value", "unit"}} of the cell's ``kind`` metrics; a
    metric whose reader finds nothing to read is left out."""
    out = {}
    for m in cell.metrics[kind]:
        mod = load_module(os.path.join(cell.root, "bench", "metrics",
                                       m["name"] + ".py"))
        v = mod.compute(rec, tr)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(rec: dict, metrics: dict, device: dict,
                breakdown: Optional[dict]) -> dict:
    """The result object; the compared numbers, with their limits, come
    last under ``checks``."""
    checks = rec["checks"]
    out = {"correct": bool(checks) and all(
               c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values()),
           "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]),
           "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
