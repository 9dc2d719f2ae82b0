"""Subprocess worker for ``benchmarks/run.py pool``.

One pool size per process: the simulated node count is an XLA device
count, which must be fixed before jax is imported, so the parent
benchmark launches one worker per cell.  Prints a JSON record on
stdout: tokens/s of the batched decode, the greedy outputs and the
prefill logits (the parent checks every pool size against the 1-node
``PagedServer`` reference to 1e-4), tier telemetry and the Ether-oN
control-plane terms.

``--mode degraded`` runs the failure cell instead: the same workload
through the PoolRouter with one node killed mid-run (plus optional
``--fault-plan`` fabric chaos) — outputs must match the uninterrupted
reference, and the record carries recovery latency and the goodput dip.

  python benchmarks/pool_worker.py --nodes 4 [--mode pool|single|degraded] \
      [--requests 6 --prompt-len 24 --gen 16] [--fault-plan lossy]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, required=True)
    ap.add_argument("--mode", choices=["pool", "single", "degraded"],
                    default="pool")
    ap.add_argument("--fault-plan", default="none",
                    help="degraded mode: seeded fabric fault plan "
                         "layered on the mid-run kill — a preset name "
                         "(none/lossy/storm), inline JSON, or a path "
                         "(repro.core.faults.load_plan)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--horizon", type=int, default=8,
                    help="fused decode-horizon length for the horizon "
                         "cell (0 disables)")
    ap.add_argument("--page-dtype", choices=["fp32", "int8", "fp8"],
                    default="fp32",
                    help="KV page storage format (quantized pages "
                         "decode through the fused-dequant kernel)")
    args = ap.parse_args()

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (f"{flags} --xla_force_host_platform_"
                               f"device_count={args.nodes}").strip()
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_arch
    from repro.core import analytical as A
    from repro.core.storage_pool import StoragePool
    from repro.models.api import get_model

    # the demo config of examples/serve_pool.py / BENCH_serve.json
    cfg = dataclasses.replace(
        get_arch("granite-3-2b"),
        n_layers=2, d_model=128, n_heads=8, n_kv_heads=4, d_ff=256,
        vocab_size=512)
    model = get_model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len,
                            dtype=np.int32) for _ in range(args.requests)]

    rec = {"nodes": args.nodes, "mode": args.mode,
           "page_dtype": args.page_dtype}

    if args.mode == "degraded":
        # -- degraded-mode cell: the main workload through the
        # PoolRouter with one DockerSSD killed mid-run (optionally
        # under --fault-plan fabric chaos).  An uninterrupted run on an
        # identically warmed stack is the reference: the chaos run must
        # finish every request with token-identical output, and the
        # record carries the recovery latency (kill -> every victim
        # sequence re-placed and decoding on a survivor) and the
        # goodput dip the failure cost.
        from repro.core.faults import load_plan
        from repro.runtime.pool import PoolServer
        from repro.runtime.scheduler import PoolRouter, Request

        def fresh():
            server = PoolServer(
                model, params, n_nodes=args.nodes,
                page_size=args.page_size,
                hbm_pages_per_node=-(-8 * args.requests // args.nodes),
                dtype=jnp.float32, page_dtype=args.page_dtype)
            pool = StoragePool(args.nodes, heartbeat_timeout=0.0)
            pool.attach_server(server)
            if args.fault_plan != "none":
                pool.attach_faults(load_plan(args.fault_plan))
            return server, pool

        def drive(server, pool, kill):
            """One full workload pass through a fresh router.  ``kill``
            fails the node owning the first active sequence once decode
            is under way (iteration 2 — mid-run, after the first
            horizon)."""
            router = PoolRouter(server, pool, max_active=args.requests,
                                horizon=max(args.horizon, 1),
                                prefill_chunk=2 * args.page_size)
            for i, p in enumerate(prompts):
                router.submit(Request(rid=i, prompt=p,
                                      max_tokens=args.gen))
            timeline = []             # (step wall s, tokens emitted)
            victims, killed, t_kill, recovery_s = [], None, None, None
            while router.waiting or router.prefilling or router.active:
                if kill and t_kill is None and router.active:
                    rid = next(iter(router.active))
                    killed = server.node_of(rid)
                    victims = [r for r in list(router.active)
                               if server.node_of(r) == killed]
                    pool.nodes[pool.serving_ips()[killed]].fail()
                    t_kill = time.perf_counter()
                t0 = time.perf_counter()
                n = router.step()
                timeline.append((time.perf_counter() - t0, n))
                if t_kill is not None and recovery_s is None:
                    done = {f.rid for f in router.finished}
                    if all(r in router.active or r in done
                           for r in victims):
                        recovery_s = time.perf_counter() - t_kill
            out = {r.rid: list(r.output) for r in router.finished}
            return out, timeline, router, killed, recovery_s

        # reference: one untimed pass warms the jit buckets (admission
        # chunks, horizon steps), then the timed uninterrupted run
        server, pool = fresh()
        drive(server, pool, kill=False)
        ref_out, ref_tl, _, _, _ = drive(server, pool, kill=False)

        # chaos: same warm-up discipline on a fresh stack (a killed
        # node cannot be revived), then the timed run with the kill
        server, pool = fresh()
        drive(server, pool, kill=False)
        out, tl, router, killed, recovery_s = drive(server, pool,
                                                    kill=True)

        assert out == ref_out, \
            "degraded run diverged from the uninterrupted reference"
        assert recovery_s is not None, "victim sequences never recovered"
        toks = args.requests * args.gen
        ref_s = sum(dt for dt, _ in ref_tl)
        deg_s = sum(dt for dt, _ in tl)
        st = pool.driver.stats
        rec["degraded"] = {
            "killed_node": killed,
            "fault_plan": args.fault_plan,
            "outputs_identical_after_kill": out == ref_out,
            "recovery_s": recovery_s,
            "requeues": router.requeues,
            "rejected": len(router.rejected),
            "ref_tokens_per_s": toks / ref_s,
            "degraded_tokens_per_s": toks / deg_s,
            "goodput_vs_uninterrupted": ref_s / deg_s,
            "steps_ref": len(ref_tl),
            "steps_degraded": len(tl),
            "retransmits": st.retransmits,
            "nacks": st.nacks,
            "dup_frames": st.dup_frames,
        }
        if pool.fault_injector is not None:
            rec["degraded"]["faults"] = \
                pool.fault_injector.stats.as_dict()
        print(json.dumps(rec))
        return

    if args.mode == "single":
        from repro.runtime.serve import PagedServer
        server = PagedServer(model, params, page_size=args.page_size,
                             hbm_pages=8 * args.requests,
                             dtype=jnp.float32,
                             page_dtype=args.page_dtype)
        pool = None
    else:
        from repro.runtime.pool import PoolServer
        server = PoolServer(
            model, params, n_nodes=args.nodes, page_size=args.page_size,
            hbm_pages_per_node=-(-8 * args.requests // args.nodes),
            dtype=jnp.float32, page_dtype=args.page_dtype)
        pool = StoragePool(args.nodes)
        pool.attach_server(server)

    # admission through the frontend (pool mode: placement rides an
    # Ether-oN control frame to the chosen node before the shard admits)
    logits = []
    for i, p in enumerate(prompts):
        if pool is not None:
            node = pool.place_sequence(i, args.prompt_len + args.gen)
            last = server.add_request(i, p, node=node)
        else:
            last = server.add_request(i, p)
        logits.append(np.asarray(last, np.float64).tolist())

    def readmit():
        for s in list(server.sequence_ids()):
            server.free_sequence(s)
        for i, p in enumerate(prompts):
            if pool is not None:
                node = pool.place_sequence(i, args.prompt_len + args.gen)
                server.add_request(i, p, node=node)
            else:
                server.add_request(i, p)

    reps = 3                          # best-of-N per cell (noise guard)

    def timed(horizon):
        """Best-of-``reps`` timed decodes from identical re-admitted
        states; the caller warms the shape buckets first, so jit
        tracing never contaminates a cell."""
        best = None
        for _ in range(reps):
            readmit()
            t0 = time.perf_counter()
            server.decode(args.gen, horizon=horizon)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
        return best

    out = server.decode(args.gen)          # warm per-token + canonical out
    dt = timed(None)

    toks = args.requests * args.gen
    rec["tokens_per_s"] = toks / dt
    rec["decode_s"] = dt
    rec["outputs"] = {int(k): [int(t) for t in v] for k, v in out.items()}

    if args.horizon > 0:
        readmit()
        out_h = server.decode(args.gen, horizon=args.horizon)   # warm
        assert out_h == out, "horizon decode diverged from per-token"
        dt_h = timed(args.horizon)
        rec["horizon"] = args.horizon
        rec["tokens_per_s_horizon"] = toks / dt_h
        rec["decode_s_horizon"] = dt_h
        rec["horizon_outputs_match"] = True
    rec["prefill_logits"] = logits
    rec["tier"] = {k: v for k, v in server.tier_stats().items()}
    if pool is not None:
        rec["node_tier"] = server.node_tier_stats()
        # control-plane terms over ONE placement round (a place frame
        # per request), not the cumulative warm-up admissions:
        # delta-account the driver stats around a single readmit, the
        # same discipline the isp bench applies to its data plane
        import copy
        import types
        s0 = copy.copy(vars(pool.driver.stats))
        readmit()
        delta = types.SimpleNamespace(**{
            k: v - s0[k] for k, v in vars(pool.driver.stats).items()})
        rec["control_plane"] = A.control_plane_terms(delta, toks)

    # -- shared-prefix cell: one system template across every request --
    # cold = the prefix cache ablated (every prompt token computed,
    # chunked); warm = the same prompts re-admitted with the cache
    # seeded by an untimed round.  Outputs must be token-identical cold
    # vs warm; in pool mode the prefix-aware placement routes every
    # sharer to the node whose index holds the template.
    chunk = 2 * args.page_size
    shared = 3 * args.prompt_len // 4
    sp_template = rng.integers(0, cfg.vocab_size, shared, dtype=np.int32)
    sp_prompts = [np.concatenate([sp_template, rng.integers(
        0, cfg.vocab_size, args.prompt_len - shared, dtype=np.int32)])
        for _ in range(args.requests)]

    def sp_free():
        for s in list(server.sequence_ids()):
            server.free_sequence(s)

    def sp_admit(ps):
        for i, p in enumerate(ps):
            if pool is not None:
                node = pool.place_sequence(
                    i, args.prompt_len + args.gen, prompt=p)
                server.add_request(i, p, node=node, chunk=chunk)
            else:
                server.add_request(i, p, chunk=chunk)

    def sp_decode():
        # one sequence at a time: the prefix-aware placement
        # concentrates the cohort on the owning node, whose window only
        # has to hold the ACTIVE working set — idle sharers' unshared
        # pages spill to that node's flash tier and page back, the
        # shared template pages never move
        pend = server.pending_tokens()
        out = {}
        for i in range(args.requests):
            out[i] = [pend[i]] + server.decode(args.gen, seqs=[i])[i]
        return out

    sp_free()
    server.prefix_cache = False
    sp_admit(sp_prompts)             # untimed cold-shape bucket warm-up
    sp_free()
    t0 = time.perf_counter()
    sp_admit(sp_prompts)
    t_cold = time.perf_counter() - t0
    out_cold = sp_decode()
    sp_free()

    server.prefix_cache = True
    sp_admit(sp_prompts)             # untimed: seeds the prefix cache
    sp_free()
    sp_admit(sp_prompts)             # untimed warm-shape bucket warm-up
    sp_free()
    s_tok0 = server.table.stats.prefix_tokens
    c_tok0 = server.prefill_tokens_computed
    t0 = time.perf_counter()
    sp_admit(sp_prompts)
    t_warm = time.perf_counter() - t0
    owner = server.node_of(0) if pool is not None else None
    saved = server.table.stats.prefix_tokens - s_tok0
    computed = server.prefill_tokens_computed - c_tok0
    out_warm = sp_decode()
    assert out_warm == out_cold, \
        "warm (shared-prefix) outputs diverged from the cold run"
    rec["shared_prefix"] = {
        "shared_fraction": shared / args.prompt_len,
        "prefill_chunk": chunk,
        "cold_admission_s": t_cold,
        "warm_admission_s": t_warm,
        "warm_speedup": t_cold / t_warm,
        "prefix_hit_rate": saved / max(saved + computed, 1),
        "prefill_tokens_per_s": {
            "cold": args.requests * args.prompt_len / t_cold,
            "warm_admitted": args.requests * args.prompt_len / t_warm,
        },
        "outputs_identical_warm_vs_cold": True,
    }
    if pool is not None:
        rec["shared_prefix"]["owner_node"] = owner
        rec["shared_prefix"]["node_prefix_hits"] = [
            ns["prefix_hits"] for ns in server.node_tier_stats()]
    sp_free()

    # -- speculative cell: prompt-lookup draft-verify vs the plain
    # horizon, the serve_decode cell's workload on this pool size.
    # Repetitive prompts carry their own continuation in the tail
    # (constant runs the demo model self-sustains), so the drafter
    # copies successors out of the prompt from the first pass; outputs
    # must stay token-identical to the plain fused horizon.
    spec_gen, spec_h = 48, 16
    spec_prompts = [np.asarray([c] * (24 + i % 2) + [t] * 16, np.int32)
                    for i, (c, t) in
                    enumerate([(41, 49), (500, 259)] * 2)]
    # the cell inherits the main workload's window, and a spec
    # sequence's whole reservation (prompt + gen pages) is resident and
    # pinned by the last pass.  Size the reservation against the
    # per-shard window: when the default gen doesn't fit one node's
    # share, fall back to the largest gen (and a matching horizon) that
    # does, instead of skipping the cell (capacity-guarded placement
    # disperses sharers once the prefix node's window fills, so a shard
    # holds at most its even cohort share).
    max_plen = max(len(p) for p in spec_prompts)
    if pool is None:
        window = 8 * args.requests
        seqs_here = len(spec_prompts)
    else:
        window = -(-8 * args.requests // args.nodes)       # one shard
        seqs_here = -(-len(spec_prompts) // args.nodes)
    gen_fit = (window // seqs_here) * args.page_size - max_plen
    spec_window_limited = gen_fit < spec_gen
    if spec_window_limited:
        spec_gen = gen_fit
        spec_h = max(1, min(spec_h, spec_gen))
    if args.horizon > 0 and spec_gen < 2:
        rec["speculative"] = {"skipped":
                              "per-node window below one sequence's "
                              "prompt pages — no gen fits"}
    if args.horizon > 0 and spec_gen >= 2:

        def spec_admit():
            sp_free()
            for i, p in enumerate(spec_prompts):
                if pool is not None:
                    node = pool.place_sequence(
                        i, len(p) + spec_gen, prompt=p)
                    server.add_request(i, p, node=node)
                else:
                    server.add_request(i, p)

        def spec_timed(horizon, speculative):
            spec_admit()
            server.decode(spec_gen, horizon=horizon,
                          speculative=speculative)     # bucket warm-up
            best, out, stats = None, None, None
            for _ in range(reps):
                spec_admit()
                server.reset_speculation_stats()
                t0 = time.perf_counter()
                o = server.decode(spec_gen, horizon=horizon,
                                  speculative=speculative)
                dt = time.perf_counter() - t0
                if best is None or dt < best:
                    best, out = dt, o
                    stats = server.speculation_stats()
            toks = sum(len(v) for v in out.values())
            return toks / best, out, stats

        base_tps, base_out, _ = spec_timed(args.horizon, False)
        spec_tps, spec_out, st = spec_timed(spec_h, True)
        assert spec_out == base_out, \
            "speculative decode diverged from the plain horizon"
        rec["speculative"] = {
            "gen": spec_gen, "spec_horizon": spec_h,
            "window_limited": spec_window_limited,
            "base_tokens_per_s": base_tps,
            "spec_tokens_per_s": spec_tps,
            "speedup_vs_horizon": spec_tps / base_tps,
            "alpha": st["alpha"],
            "passes": st["passes"],
            "fallback_passes": st["fallback_passes"],
            "accepted_len_hist": {str(k): v for k, v
                                  in st["accepted_len_hist"].items()},
            "outputs_identical": True,
        }
        sp_free()

    # -- latency percentiles: the main workload through the continuous
    # batcher (iteration-level admission) — per-request p50/p99 TTFT
    # and TPOT, the traffic-facing face of the aggregate tok/s above
    from repro.runtime.scheduler import (ContinuousBatcher, PoolRouter,
                                         Request)

    def lat_run():
        sp_free()
        kw = dict(max_active=args.requests,
                  horizon=max(args.horizon, 1),
                  prefill_chunk=2 * args.page_size)
        sched = (PoolRouter(server, pool, **kw) if pool is not None
                 else ContinuousBatcher(server, **kw))
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=p, max_tokens=args.gen))
        return sched.run_to_completion()

    # two untimed warm-ups: the first traces cache-cold buckets and
    # seeds the prefix cache, the second traces the warm-hit buckets
    # the steady-state (timed) run actually uses
    lat_run()
    lat_run()
    lat = lat_run()
    rec["latency"] = {k: lat[k] for k in
                      ("requests", "mean_ttft_s", "p50_ttft_s",
                       "p99_ttft_s", "mean_tpot_s", "p50_tpot_s",
                       "p99_tpot_s", "mean_latency_s", "p99_latency_s")}
    sp_free()
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
