"""A cell, mix, configuration and metric defined only by new files (and
new BENCHMARK.json entries) are found by name."""
import json
import os

from bench import harness


def test_new_cell_found_by_name(tiny_root):
    cell = harness.find_cell("tiny-chat", tiny_root)
    assert cell.config["name"] == "tiny-lm"
    assert cell.cell["driver"] == "serve"
    assert cell.mix["arrivals"]["rate_per_s"] == 4.0
    names = {m["name"] for m in cell.metrics["end_to_end"]}
    assert names == {"output_tok_s", "setup_s"}
    assert harness.driver_module(cell).run


def test_new_metric_found_by_name(tiny_root, tmp_path):
    import shutil
    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    with open(os.path.join(root, "bench", "metrics",
                           "requests_done.serve.py"), "w") as f:
        f.write('"""requests_done.serve: requests finished."""\n\n\n'
                'def compute(rec, tr):\n'
                '    return sum(1 for r in rec["requests"] if r["done"])\n')
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append(
        {"name": "requests_done.serve", "unit": "requests",
         "better": "higher", "source": "host_clock", "layer": "scheduler",
         "moves": "output_tok_s", "workloads": ["tiny-chat"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = harness.find_cell("tiny-chat", root)
    assert "requests_done.serve" in [m["name"]
                                     for m in cell.metrics["per_layer"]]
    cell.metrics["per_layer"] = [m for m in cell.metrics["per_layer"]
                                 if m["name"] == "requests_done.serve"]
    rec = {"requests": [{"done": 1.0}, {"done": None}, {"done": 2.0}],
           "trace_window": (0, 1)}
    got = harness.compute_metrics(cell, "per_layer", rec, {"modules": {},
                                                           "kernels": {}})
    assert got["requests_done.serve"] == {"value": 2.0, "unit": "requests"}


def test_unknown_cell_is_an_error(tiny_root):
    import pytest
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell", tiny_root)
