"""Fixtures for the benchmark's CPU tests: a tiny benchmark root.

``tiny_root`` is a copy of ``bench/`` beside a BENCHMARK.json that adds
tiny cells (a 2-layer, width-64 LM) defined only by new files — what a
later PR adding a cell would add.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LM = dict(name="tiny-lm", num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=128, vocab_size=128,
               tie_word_embeddings=True, hidden_act="silu",
               rope_theta=10000.0, rms_norm_eps=1e-6, reduced=[])
SERVE_CELL = {"driver": "serve",
              "server": {"page_size": 16, "hbm_pages": 64,
                         "dtype": "float32"},
              "scheduler": {"max_active": 4, "horizon": 8,
                            "prefill_chunk": 64},
              "lead_s": 0.5, "drain_s": 20, "trace_s": 1,
              "check": {"requests": 3,
                        "limits": {"served_gap_max": 1e-4}}}
LENGTHS = {"prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 8, "max": 48},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                      "min": 9, "max": 33, "round_up_to": 8, "plus": 1}}


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def build_root(root: str) -> str:
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(root, "bench")
    _dump(TINY_LM, b, "configs", "tiny-lm.json")
    _dump(SERVE_CELL, b, "cells", "tiny-chat.json")
    _dump(dict(SERVE_CELL, drain_s=0), b, "cells", "tiny-batch.json")
    _dump(dict(LENGTHS, arrivals={"kind": "poisson", "rate_per_s": 4.0}),
          b, "mixes", "tiny-chat.json")
    _dump(dict(LENGTHS, arrivals={"kind": "closed", "clients": 4,
                                  "requests_per_client": 64}),
          b, "mixes", "tiny-batch.json")
    spec["configs"] += [
        {"name": "tiny-lm", "source": "test", "file":
         "bench/configs/tiny-lm.json", "reduced": [], "why": "test"}]
    # the tiny cells report what granite-decode-batch reports; tiny-chat
    # keeps the open-loop path of the driver and generator under test
    twin = {"granite-decode-batch": ["tiny-batch", "tiny-chat"]}
    spec["workloads"] += [
        {"name": n, "config": "tiny-lm", "traffic": n, "chips": 1,
         "why": "test"} for n in ("tiny-chat", "tiny-batch")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [t for w in m["workloads"]
                                               for t in twin[w]]
    _dump(spec, root, "BENCHMARK.json")
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return build_root(str(tmp_path_factory.mktemp("bench_root")))


def quiet(msg: str) -> None:
    pass
