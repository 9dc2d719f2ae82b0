"""Serving launcher — batched-request decode with the D-Cache runtime.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --requests 4 --prompt-len 16 --gen 32 [--paged | --pool] \
      [--horizon 8 --speculative] [--temperature 0.8 --top-p 0.9]

Three paths:

  * default — dense jitted decode (what the dry-run lowers at
    production scale).
  * ``--paged`` — the tiered PagedKVCache + Pallas paged_attention path
    on one device (the paper's mechanism made concrete).
  * ``--pool`` — distributed pool serving: a ``PoolServer`` shard-maps
    the tiered decode over ``--nodes`` devices (one DockerSSD node per
    ``model``-axis shard), fronted by a ``StoragePool`` whose
    admission/placement/free control messages ride Ether-oN frames and
    a ``PoolRouter`` doing least-loaded placement, per-node admission
    and failover requeue.  To simulate N nodes on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before
    launching (default ``--nodes 0`` uses every visible device).

Timing uses ``time.monotonic()`` so reported throughput/latency cannot
be skewed (or go negative) by wall-clock adjustment mid-run.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model
from repro.runtime.serve import PagedServer, make_serving_fns


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--pool", action="store_true",
                    help="distributed pool serving (PoolServer across "
                         "--nodes devices; see module docstring)")
    ap.add_argument("--nodes", type=int, default=0,
                    help="pool size; 0 = all visible devices")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--hbm-pages", type=int, default=32,
                    help="HBM window pages (per node with --pool)")
    ap.add_argument("--page-dtype", choices=["fp32", "int8", "fp8"],
                    default="fp32",
                    help="KV page storage format (--paged / --pool): "
                         "int8/fp8 store quantized codes + per-slot f32 "
                         "scales (~3x smaller pages) and decode through "
                         "the fused-dequant attention kernel")
    ap.add_argument("--horizon", type=int, default=1,
                    help="fused decode-horizon length: tokens generated "
                         "per host interaction (--paged / --pool; 1 = "
                         "classic per-token scheduling)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-verify decoding on the fused-horizon "
                         "scaffold (--paged / --pool, needs --horizon "
                         ">= 2): a device-side prompt-lookup drafter "
                         "proposes up to horizon-1 tokens, one "
                         "chunk-shaped pass verifies them, and the "
                         "accepted prefix + bonus token commit; "
                         "outputs are token-identical to the plain "
                         "horizon (greedy) or distribution-correct "
                         "(rejection sampling, temperature > 0)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax); "
                         "sampling runs on-device, seeded, so reruns "
                         "and pool nodes reproduce the same tokens")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (only with "
                         "--temperature > 0)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill size: admissions run at most "
                         "this many prompt tokens per scheduler "
                         "iteration, interleaved with decode horizons "
                         "(--paged / --pool; 0 = blocking one-shot "
                         "admission).  Prompts sharing a cached prefix "
                         "skip the covered pages entirely")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.speculative and not (args.paged or args.pool):
        raise SystemExit("--speculative needs --paged or --pool")
    if args.speculative and args.horizon < 2:
        raise SystemExit("--speculative needs --horizon >= 2 (the "
                         "draft rides the fused-horizon scaffold)")
    sampling = None
    if args.temperature > 0:
        from repro.runtime.serve import SamplingConfig
        sampling = SamplingConfig(temperature=args.temperature,
                                  top_p=args.top_p)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg, compute_dtype=jnp.float32, moe_no_drop=True)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len), dtype=np.int32)

    t0 = time.monotonic()
    if args.pool:
        if cfg.block_type != "transformer":
            raise SystemExit("--pool demo path supports transformer archs")
        from repro.core import analytical as A
        from repro.core.storage_pool import StoragePool
        from repro.runtime.pool import PoolServer
        from repro.runtime.scheduler import PoolRouter, Request
        n = args.nodes or len(jax.devices())
        server = PoolServer(model, params, n_nodes=n,
                            page_size=args.page_size,
                            hbm_pages_per_node=args.hbm_pages,
                            page_dtype=args.page_dtype)
        pool = StoragePool(n)
        pool.attach_server(server)
        router = PoolRouter(server, pool, max_active=args.requests,
                            horizon=args.horizon,
                            speculative=args.speculative,
                            sampling=sampling,
                            prefill_chunk=args.prefill_chunk or None)
        for i in range(args.requests):
            router.submit(Request(rid=i, prompt=prompts[i],
                                  max_tokens=args.gen))
        stats = router.run_to_completion()
        toks = sum(len(r.output) for r in router.finished)
        print(f"pool of {n} nodes | per-node tier stats: "
              f"{server.node_tier_stats()}")
        print("aggregate tier stats:", stats["tier"])
        print("control plane:", A.control_plane_terms(pool.driver.stats,
                                                      toks))
    elif args.paged:
        if cfg.block_type != "transformer":
            raise SystemExit("--paged demo path supports transformer archs")
        server = PagedServer(model, params, page_size=args.page_size,
                             hbm_pages=args.hbm_pages,
                             page_dtype=args.page_dtype)
        for i in range(args.requests):
            server.add_request(i, prompts[i],
                               chunk=args.prefill_chunk or None)
        out = server.decode(args.gen,
                            horizon=args.horizon if args.horizon > 1
                            else None,
                            sampling=sampling,
                            speculative=args.speculative)
        toks = sum(len(v) for v in out.values())
        if args.speculative:
            st = server.speculation_stats()
            print(f"speculation: alpha={st['alpha']:.2f} "
                  f"passes={st['passes']} "
                  f"(fallback {st['fallback_passes']}) "
                  f"accepted-length hist {st['accepted_len_hist']}")
        print("tier stats:", server.tier_stats())
        print(f"prefix hit rate: {server.prefix_hit_rate():.2f} "
              f"(prompt tokens served from the shared-prefix cache)")
    else:
        prefill, decode = make_serving_fns(model)
        total = args.prompt_len + args.gen
        logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompts)})
        # grow cache to generation capacity
        if "k" in cache:
            pad = total - cache["k"].shape[-2]
            cache["k"] = jnp.pad(cache["k"],
                                 [(0, 0)] * 3 + [(0, pad), (0, 0)])
            cache["v"] = jnp.pad(cache["v"],
                                 [(0, 0)] * 3 + [(0, pad), (0, 0)])
        if sampling is not None:
            from repro.runtime.serve import sampling_log_probs
            key = jax.random.PRNGKey(sampling.seed)

        def pick(lg, step):
            if sampling is None:
                return jnp.argmax(lg, -1).astype(jnp.int32)
            lp = sampling_log_probs(lg, jnp.float32(sampling.temperature),
                                    jnp.float32(sampling.top_p))
            g = jax.random.gumbel(jax.random.fold_in(key, step),
                                  lp.shape, jnp.float32)
            return jnp.argmax(lp + g, -1).astype(jnp.int32)

        toks = 0
        cur = pick(logits, 0)
        for step in range(args.gen):
            logits, cache = decode(params, cache, cur)
            cur = pick(logits, step + 1)
            toks += args.requests
    dt = time.monotonic() - t0
    print(f"served {args.requests} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
