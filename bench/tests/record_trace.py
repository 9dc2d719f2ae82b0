"""Record the small device trace that ``bench/tests/test_trace.py`` reduces.

  python bench/tests/record_trace.py OUT_DIR

Needs a TPU.  granite-3-2b's widths cut to 2 layers serve 4 requests
through ``ContinuousBatcher`` (one prefill chunk per iteration and the
H=8 decode horizon, both on the Pallas paged-attention kernel); then one
top-k job scans a small extent through the ISP front door.  The profiler
records two scheduler iterations, a short sleep and the job, each under
a host span (``bench.step``, ``bench.wait``, ``bench.submit_jobs``).
``bench/tests/data/probe.xplane.pb`` is such a trace.  Writes the trace
under ``OUT_DIR/trace`` and a summary of every plane, line and event
name to ``OUT_DIR/summary.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402


def summarize(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            first = [{"name": e.name, "start_ns": e.start_ns,
                      "dur_ns": e.duration_ns,
                      "stats": {k: str(v)[:120] for k, v in e.stats}}
                     for e in evs[:3]]
            lines.append({"line": line.name, "n": len(evs),
                          "names": dict(sorted(names.items(),
                                               key=lambda kv: -kv[1])[:40]),
                          "first": first})
        out.append({"plane": plane.name,
                    "stats": {k: str(v)[:120] for k, v in plane.stats},
                    "lines": lines})
    return {"planes": out}


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.configs.base import get_arch
    from repro.core import AnalyticsJob, StoragePool, analytics_blob
    from repro.core import SHARABLE_NS
    from repro.models.api import get_model
    from repro.runtime.scheduler import ContinuousBatcher, Request
    from repro.runtime.serve import PagedServer

    cfg = dataclasses.replace(get_arch("granite-3-2b"), n_layers=2)
    model = get_model(cfg, compute_dtype=jnp.bfloat16)
    params = jax.jit(lambda k: model.init(k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(0))
    server = PagedServer(model, params, page_size=16, hbm_pages=256,
                         dtype=jnp.bfloat16)
    batcher = ContinuousBatcher(server, max_active=8, horizon=8,
                                prefill_chunk=256)
    rng = np.random.default_rng(0)
    for rid in range(4):
        batcher.submit(Request(rid, rng.integers(0, cfg.vocab_size, 200,
                                                 dtype=np.int32), 33))
    for _ in range(6):                       # compile every shape first
        batcher.step()
    for rid in range(4, 8):
        batcher.submit(Request(rid, rng.integers(0, cfg.vocab_size, 200,
                                                 dtype=np.int32), 33))
    for _ in range(6):
        batcher.step()

    pool = StoragePool(1, extent_cfg={"n_pages": 64, "page_rows": 128,
                                      "n_cols": 128})
    pool.broadcast_pull("isp-analytics", analytics_blob())
    ip = pool.alive_nodes()[0]
    node = pool.nodes[ip]
    data = rng.standard_normal((64 * 128 - 5, 100), dtype=np.float32)
    node.fs.write("/data/t.bin", data.tobytes(), SHARABLE_NS, actor="host")
    node.ingest_extent("t", "/data/t.bin", 100)
    q = rng.standard_normal(100).astype(np.float32)
    q /= np.linalg.norm(q)
    job = AnalyticsJob(extent="t", reduce="topk", k=10, metric="cosine",
                       query=[float(x) for x in q]).to_dict()
    pool.driver.submit_jobs(ip, [job])       # compile

    trace_dir = os.path.join(out_dir, "trace")
    t0 = time.monotonic()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans (TraceMe) stay
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_start = time.monotonic()
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.step"):
            batcher.step()
    with jax.profiler.TraceAnnotation("bench.wait"):
        time.sleep(0.002)
    with jax.profiler.TraceAnnotation("bench.submit_jobs"):
        pool.driver.submit_jobs(ip, [job])
    t_stop = time.monotonic()
    jax.profiler.stop_trace()
    print(f"traced window {t_stop - t_start!r} s (start_trace "
          f"{t_start - t0!r} s)")
    files = []
    for root, _, names in os.walk(trace_dir):
        files += [os.path.join(root, n) for n in names]
    print("files:", [(f, os.path.getsize(f)) for f in files])
    xp = [f for f in files if f.endswith(".xplane.pb")][0]
    summary = summarize(xp)
    summary["host_window_s"] = t_stop - t_start
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for p in summary["planes"]:
        print("PLANE", p["plane"], p["stats"])
        for ln in p["lines"]:
            print("   LINE", ln["line"], ln["n"], list(ln["names"])[:12])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
