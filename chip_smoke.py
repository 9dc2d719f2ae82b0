"""Chip smoke run: the system's two main paths, once, on TPU, at real size.

  python chip_smoke.py            # one chip: pool serving + in-storage scans
  python chip_smoke.py --chips 4  # four chips: 4-node pool vs 1-node server

Serving phase: granite-3-2b at its published width (40 layers, d_model
2048, 32 query / 8 KV heads, d_ff 8192, vocab 49,155) in bf16 with
random weights from ``--seed`` serves 8 requests of 300 prompt tokens
and 34 generated tokens through ``PagedServer`` (chunked prefill, one
per-token decode step, then the fused decode horizon H=8).  It is
checked against the dense jnp model (``make_serving_fns``) on the same
device: prefill and first-step logits within a stated bf16 tolerance,
and every generated token the reference is decisive about.  The
compiled horizon program must contain the Pallas kernel
(``tpu_custom_call``).

ISP phase: 256 MiB of extent pages on one DockerSSD node's
``ExtentStore``; one filter/reduce and one top-k ``AnalyticsJob`` go
through the docker-cli front door, and each result must be
bit-identical to the host fold of the same data.

``--chips 4`` runs only the pool path: granite-3-2b on a 4-node
``PoolServer`` (one node per chip) against the 1-node ``PagedServer``
on the same prompts — identical greedy tokens, prefill logits within
tolerance — and reports where each node's extent pages live.

Earlier lines report the device, shapes, compile seconds and per-phase
wall times; the last line is ``{"ok": true, "device": {...}}``.  Without
a TPU the script exits non-zero before any phase runs: it never falls
back to the CPU.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import urllib.parse

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

#: serving workload (per request): prompt tokens, generated tokens, the
#: fused horizon; 1 prefill token + 1 per-token step + 4 horizons of 8
SERVE = dict(n_requests=8, prompt_len=300, gen=34, horizon=8,
             page_size=16, hbm_pages=256)
#: served-vs-dense logits: relative RMS error bound.  Both sides run
#: bf16 weights and activations through different attention kernels,
#: so they agree to bf16 rounding (2^-8 relative per op, compounded
#: over 40 layers), not bit for bit; a wrong kernel (head mapping,
#: masking, page order) is off by O(1).
LOGIT_REL_RMS_TOL = 5e-2
#: a generated token is checked against the dense model's argmax when
#: the dense top-2 gap exceeds this many times the measured first-step
#: max |logit difference| (smaller gaps are bf16 near-ties)
DECISIVE_FACTOR = 4.0
#: in-storage analytics: 4096 pages x 128 rows x 128 f32 cols = 256 MiB
ISP = dict(n_pages=4096, page_rows=128, n_cols=128, k=10)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def init_params(model, seed: int, dtype):
    """Random weights from ``seed`` in ``dtype``, made on the device."""
    return jax.jit(functools.partial(model.init, dtype=dtype))(
        jax.random.PRNGKey(seed))


def logit_errors(got, want) -> dict:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = got - want
    return {"rel_rms": float(np.sqrt(np.mean(diff ** 2)) /
                             np.sqrt(np.mean(want ** 2))),
            "max_abs": float(np.abs(diff).max()),
            "argmax_agree": float(np.mean(got.argmax(-1) ==
                                          want.argmax(-1)))}


def _check_logits(name, errs):
    log(f"  {name}: rel-RMS {errs['rel_rms']!r} (tol {LOGIT_REL_RMS_TOL}),"
        f" max|diff| {errs['max_abs']!r}, argmax agree "
        f"{errs['argmax_agree']!r}")
    assert errs["rel_rms"] <= LOGIT_REL_RMS_TOL, \
        f"{name}: served logits off the dense reference"


def horizon_program_text(server, batch: int, pages: int, horizon: int):
    """Compile the server's fused-horizon program at the (pow2-bucketed)
    shapes the decode loop will run and return (HLO text, seconds)."""
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    t0 = time.monotonic()
    compiled = server._horizon_jit.lower(
        server.params, server.store.device_state(), i32((batch, pages)),
        i32((batch,)), i32((batch,)), i32((batch,)), i32(()),
        jax.random.PRNGKey(0), f32, f32, i32((batch,)),
        horizon=horizon).compile()
    return compiled.as_text(), time.monotonic() - t0


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def serving_phase(cfg, *, dtype=jnp.bfloat16, seed=0, n_requests, prompt_len,
                  gen, horizon, page_size, hbm_pages) -> dict:
    """Serve ``n_requests`` through PagedServer and check them against
    the dense jnp model.  Returns a record of what was measured."""
    from repro.models.api import get_model
    from repro.runtime.serve import PagedServer, make_serving_fns

    assert (gen - 2) % horizon == 0, "decode runs whole horizons"
    rec = {}
    t0 = time.monotonic()
    model = get_model(cfg, compute_dtype=dtype)
    params = jax.block_until_ready(init_params(model, seed, dtype))
    rec["param_bytes"] = int(sum(x.nbytes for x in
                                 jax.tree.leaves(params)))
    rec["init_s"] = time.monotonic() - t0
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)

    server = PagedServer(model, params, page_size=page_size,
                         hbm_pages=hbm_pages, dtype=dtype)
    text, rec["horizon_compile_s"] = horizon_program_text(
        server, _pow2(n_requests),
        _pow2(-(-(prompt_len + gen + horizon) // page_size)), horizon)
    rec["decode_has_kernel"] = "tpu_custom_call" in text

    t0 = time.monotonic()
    prefill = np.stack([np.asarray(server.add_request(i, prompts[i]))
                        for i in range(n_requests)])
    rec["prefill_s"] = time.monotonic() - t0
    first = prefill.argmax(-1).astype(np.int32)
    t0 = time.monotonic()
    _, step = server.step_batch({i: int(first[i])
                                 for i in range(n_requests)})
    step = np.asarray(step)
    rec["first_step_s"] = time.monotonic() - t0
    second = step.argmax(-1).astype(np.int32)
    for i in range(n_requests):
        server.set_pending(i, int(second[i]))
    t0 = time.monotonic()
    out = server.decode(gen - 2, horizon=horizon)
    rec["horizon_decode_s"] = time.monotonic() - t0
    tokens = np.asarray([[first[i], second[i]] + out[i]
                         for i in range(n_requests)], np.int32)
    assert tokens.shape == (n_requests, gen), tokens.shape
    rec["tokens_served"] = int(tokens.size)

    # dense jnp reference on the same device, teacher-forced with the
    # served tokens
    t0 = time.monotonic()
    prefill_fn, decode_fn = make_serving_fns(model)
    ref_pre, cache = prefill_fn(params, {"tokens": jnp.asarray(prompts)})
    pad = [(0, 0)] * 3 + [(0, gen), (0, 0)]
    cache = dict(cache, k=jnp.pad(cache["k"], pad),
                 v=jnp.pad(cache["v"], pad))
    ref_logits = []
    for t in range(gen - 1):
        lg, cache = decode_fn(params, cache, jnp.asarray(tokens[:, t]))
        ref_logits.append(np.asarray(lg, np.float32))
    rec["reference_s"] = time.monotonic() - t0

    rec["prefill_err"] = logit_errors(prefill, ref_pre)
    rec["first_step_err"] = logit_errors(step, ref_logits[0])
    margin = DECISIVE_FACTOR * rec["first_step_err"]["max_abs"]
    checked = agreed = 0
    for t, lg in enumerate(ref_logits):
        top2 = np.sort(lg, axis=-1)[:, -2:]
        decisive = (top2[:, 1] - top2[:, 0]) > margin
        checked += int(decisive.sum())
        agreed += int((lg.argmax(-1) == tokens[:, t + 1])[decisive].sum())
    rec["decisive_tokens"] = {"checked": checked, "agreed": agreed,
                              "of": int(n_requests * (gen - 1)),
                              "margin": margin}
    return rec


def run_serving(cfg, serve) -> None:
    log(f"serving: {cfg.name} L={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} hd={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} bf16; {serve['n_requests']} requests x "
        f"{serve['prompt_len']} prompt + {serve['gen']} generated tokens, "
        f"H={serve['horizon']}, page={serve['page_size']}, "
        f"window={serve['hbm_pages']} pages")
    t0 = time.monotonic()
    rec = serving_phase(cfg, **serve)
    log(f"  weights {rec['param_bytes']} bytes, init {rec['init_s']!r} s; "
        f"horizon program compile {rec['horizon_compile_s']!r} s")
    log(f"  prefill {rec['prefill_s']!r} s | first step "
        f"{rec['first_step_s']!r} s | {serve['gen'] - 2} tokens/request in "
        f"H={serve['horizon']} horizons {rec['horizon_decode_s']!r} s | "
        f"dense reference {rec['reference_s']!r} s")
    log(f"  tpu_custom_call in compiled decode step: "
        f"{rec['decode_has_kernel']}")
    assert rec["decode_has_kernel"], "decode step has no Pallas kernel"
    _check_logits("prefill logits vs dense", rec["prefill_err"])
    _check_logits("first-step logits vs dense", rec["first_step_err"])
    dt = rec["decisive_tokens"]
    log(f"  decisive generated tokens: {dt['agreed']}/{dt['checked']} agree "
        f"with the dense argmax (of {dt['of']}; margin {dt['margin']!r})")
    assert dt["agreed"] == dt["checked"], "served tokens off the reference"
    log(f"  served {rec['tokens_served']} tokens; phase wall "
        f"{time.monotonic() - t0!r} s")


def isp_phase(*, n_pages, page_rows, n_cols, k, seed=0) -> dict:
    """Ingest ``n_pages`` of extent data on one node, run a filter/reduce
    and a top-k job through the docker-cli front door, and compare each
    with the host fold.  Returns a record of what was measured."""
    from repro.core import (AnalyticsJob, SHARABLE_NS, StoragePool,
                            analytics_blob, from_jsonable)
    from repro.kernels import ops

    rec = {}
    t0 = time.monotonic()
    pool = StoragePool(1, extent_cfg={"n_pages": n_pages,
                                      "page_rows": page_rows,
                                      "n_cols": n_cols})
    pool.broadcast_pull("isp-analytics", analytics_blob())
    node = pool.nodes[pool.alive_nodes()[0]]
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n_pages * page_rows, n_cols),
                               dtype=np.float32)
    node.fs.write("/data/tbl.bin", data.tobytes(), SHARABLE_NS,
                  actor="host")
    node.ingest_extent("tbl", "/data/tbl.bin", n_cols)
    store = node.extents
    jax.block_until_ready(store.pages)
    rec["ingest_s"] = time.monotonic() - t0
    rec["extent_bytes"] = int(store.pages.nbytes)
    rec["pages_on"] = sorted(str(d) for d in store.pages.devices())

    query = rng.standard_normal(n_cols, dtype=np.float32)
    jobs = {
        "filter_reduce": AnalyticsJob(extent="tbl", filter_col=3,
                                      filter_op="ge", threshold=0.5,
                                      reduce="table"),
        "topk": AnalyticsJob(extent="tbl", reduce="topk", k=k,
                             query=[float(x) for x in query]),
    }
    device = {}
    for name, job in jobs.items():
        t0 = time.monotonic()
        cid = json.loads(node.docker.handle_http(
            "POST /containers/create?image=isp-analytics"))["Id"]
        q = urllib.parse.quote(json.dumps([job.to_dict()]))
        resp = from_jsonable(json.loads(node.docker.handle_http(
            f"POST /containers/{cid}/start?job={q}")))
        device[name] = np.asarray(resp["result"][0], np.float32)
        rec[f"{name}_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    host_data = jnp.asarray(data)
    host = {
        "filter_reduce": ops.scan_filter_reduce_host(
            host_data, 0.5, page_rows=page_rows, filter_col=3,
            filter_op="ge"),
        "topk": ops.topk_scan_host(host_data, query, page_rows=page_rows,
                                   k=k),
    }
    host = {n: np.asarray(v) for n, v in host.items()}
    rec["host_fold_s"] = time.monotonic() - t0
    for name in jobs:
        rec[f"{name}_identical"] = bool(np.array_equal(device[name],
                                                       host[name]))
        rec[f"{name}_max_abs_diff"] = float(np.abs(device[name] -
                                                   host[name]).max())
    rec["count"] = float(device["filter_reduce"][0, 0])
    rec["topk_ids"] = [int(i) for i in device["topk"][1, :k]]
    return rec


def run_isp(isp) -> None:
    log(f"isp: {isp['n_pages']} pages x {isp['page_rows']} rows x "
        f"{isp['n_cols']} f32 cols on one node; filter/reduce + top-{isp['k']}"
        f" through the docker-cli front door")
    t0 = time.monotonic()
    rec = isp_phase(**isp)
    log(f"  extent pages: {rec['extent_bytes']} bytes on {rec['pages_on']}, "
        f"ingest {rec['ingest_s']!r} s")
    assert rec["extent_bytes"] >= 256 << 20
    log(f"  filter/reduce (col3 >= 0.5): count {rec['count']!r} in "
        f"{rec['filter_reduce_s']!r} s (first call, incl. compile); "
        f"bit-identical to host fold: {rec['filter_reduce_identical']}")
    log(f"  top-{isp['k']}: ids {rec['topk_ids']} in {rec['topk_s']!r} s "
        f"(first call, incl. compile); bit-identical to host fold: "
        f"{rec['topk_identical']}")
    log(f"  host folds {rec['host_fold_s']!r} s; phase wall "
        f"{time.monotonic() - t0!r} s")
    for name in ("filter_reduce", "topk"):
        assert rec[f"{name}_identical"], \
            f"{name}: in-storage result differs from the host fold " \
            f"(max |diff| {rec[name + '_max_abs_diff']!r})"


def pool_phase(cfg, *, n_nodes, dtype=jnp.bfloat16, seed=0, n_requests,
               prompt_len, gen, horizon, page_size, hbm_pages) -> dict:
    """The same requests through the 1-node PagedServer and an
    ``n_nodes`` PoolServer (prefill token + ``gen - 2`` horizon tokens
    each).  Returns a record of what was measured."""
    from repro.models.api import get_model
    from repro.runtime.pool import PoolServer
    from repro.runtime.serve import PagedServer

    rec = {}
    model = get_model(cfg, compute_dtype=dtype)
    params = init_params(model, seed, dtype)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)

    def serve(server):
        t0 = time.monotonic()
        logits = np.stack([np.asarray(server.add_request(i, prompts[i]))
                           for i in range(n_requests)])
        out = server.decode(gen - 2, horizon=horizon)
        toks = [[int(logits[i].argmax())] + out[i] for i in range(n_requests)]
        return logits, toks, time.monotonic() - t0

    ref = PagedServer(model, params, page_size=page_size,
                      hbm_pages=hbm_pages, dtype=dtype)
    ref_logits, ref_tokens, rec["single_s"] = serve(ref)
    del ref
    pool = PoolServer(model, params, n_nodes=n_nodes, page_size=page_size,
                      hbm_pages_per_node=hbm_pages, dtype=dtype)
    logits, tokens, rec["pool_s"] = serve(pool)
    rec["placement"] = [pool.node_of(i) for i in range(n_requests)]
    rec["kv_pages_on"] = sorted(
        str(s.device) for s in pool.store.k_pages.addressable_shards)
    rec["prefill_err"] = logit_errors(logits, ref_logits)
    rec["prefill_max_abs"] = rec["prefill_err"]["max_abs"]
    rec["tokens_match"] = tokens == ref_tokens
    rec["tokens_served"] = int(sum(len(t) for t in tokens))

    # where does each DockerSSD node's analytics data live?
    from repro.core import StoragePool
    nodes = StoragePool(n_nodes, extent_cfg={"n_pages": 4, "page_rows": 8,
                                             "n_cols": 128})
    rec["extent_pages_on"] = {
        ip: sorted(str(d) for d in node.extents.pages.devices())
        for ip, node in nodes.nodes.items()}
    return rec


def run_pool(cfg, serve, n_nodes) -> None:
    log(f"pool: {cfg.name} full width bf16 on a {n_nodes}-node PoolServer "
        f"(one node per chip) vs the 1-node PagedServer; "
        f"{serve['n_requests']} requests x {serve['prompt_len']} prompt + "
        f"{serve['gen']} generated tokens, H={serve['horizon']}")
    t0 = time.monotonic()
    rec = pool_phase(cfg, n_nodes=n_nodes, **serve)
    log(f"  1-node {rec['single_s']!r} s | {n_nodes}-node "
        f"{rec['pool_s']!r} s (both incl. compile)")
    log(f"  placement {rec['placement']}; KV page shards on "
        f"{rec['kv_pages_on']}")
    log(f"  extent pages per node: {rec['extent_pages_on']}")
    _check_logits("pool prefill logits vs 1-node", rec["prefill_err"])
    log(f"  greedy tokens identical to 1-node: {rec['tokens_match']} "
        f"({rec['tokens_served']} tokens); phase wall "
        f"{time.monotonic() - t0!r} s")
    assert rec["tokens_match"], "pool tokens diverged from the 1-node server"
    assert len(set(rec["placement"])) == n_nodes, \
        "requests did not spread over every node"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving + ISP phases on one chip; 4: only "
                         "the 4-node pool path and its 1-node reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {dev}", file=sys.stderr)
        return 2

    from repro.configs.base import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    cfg = get_arch("granite-3-2b")
    serve = dict(SERVE, seed=args.seed)
    t0 = time.monotonic()
    if args.chips == 4:
        run_pool(cfg, serve, n_nodes=4)
    else:
        run_serving(cfg, serve)
        run_isp(dict(ISP, seed=args.seed))
    log(f"total wall {time.monotonic() - t0!r} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
