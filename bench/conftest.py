"""pytest set-up for ``bench/``: lets the tests' tiny benchmark root carry
a cell that ``bench/tests/conftest.py`` has no tiny twins for.

Its ``build_root`` appends, to every metric's ``workloads``, the tiny
twins of each cell listed there, and knows twins of
granite-decode-batch alone: a cell appended to a metric's list raises
``KeyError`` there.  The wrapper below builds the root from a copy of
``BENCHMARK.json`` whose lists hold only the cells with twins, then
puts the other cells back in the tiny root's lists, where they were.
"""
from __future__ import annotations

import json
import os
import tempfile

#: the cells ``bench/tests/conftest.py`` gives tiny twins
TWINNED = ("granite-decode-batch",)


def _wrap(tiny):
    build = tiny.build_root
    if getattr(build, "wrapped", False):
        return

    def build_root(root: str) -> str:
        with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
        kept = {}
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in m:
                kept[m["name"]] = m["workloads"]
                m["workloads"] = [w for w in m["workloads"] if w in TWINNED]
        real = tiny.ROOT
        with tempfile.TemporaryDirectory() as tmp:
            with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
                json.dump(spec, f)
            tiny.ROOT = tmp
            try:
                build(root)
            finally:
                tiny.ROOT = real
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            built = json.load(f)
        for m in built["end_to_end"] + built["per_layer"]:
            if m["name"] in kept:
                n = sum(w in TWINNED for w in kept[m["name"]])
                m["workloads"] = kept[m["name"]] + m["workloads"][n:]
        with open(path, "w") as f:
            json.dump(built, f)
        return root

    build_root.wrapped = True
    tiny.build_root = build_root


def pytest_plugin_registered(plugin, manager):
    path = getattr(plugin, "__file__", None) or ""
    if path.endswith(os.path.join("bench", "tests", "conftest.py")):
        _wrap(plugin)


# pytest loads ``bench/tests/conftest.py`` as a plugin module of its
# own; test modules that import it by name get ``bench.tests.conftest``
from bench.tests import conftest as _named  # noqa: E402

_wrap(_named)
