"""Subprocess worker for the ``benchmarks/run.py pool`` autoscale cell.

Open-loop Poisson traffic against an elastic pool: a seeded arrival
process (exponential inter-arrivals on the scheduler-iteration clock,
so the trace replays bit-for-bit) walks through three phases —

  * **steady**: a rate the initial serving set handles inside SLO
    (this phase also calibrates the declared TTFT target),
  * **burst**: several times the steady rate — the backlog breaches the
    SLO and the :class:`~repro.runtime.autoscaler.Autoscaler` grows the
    serving set one node per cooldown,
  * **cooldown**: a trickle — sustained headroom drains the pool back
    down with live sequences still decoding (the zero-drop invariant).

Open-loop means arrivals NEVER wait for completions: the generator
submits on schedule whether or not the pool is keeping up, which is
what makes queue depth an honest SLO signal.

The record carries per-phase p50/p99 TTFT/TPOT (requests bucketed by
arrival phase), every scale decision, the SLO-recovery latencies, the
MIGRATE counters and the analytical migration terms.  Hard floors are
asserted in-process — zero shed requests, at least one scale-up and one
drain, a recorded breach->healthy recovery, and exactly zero MIGRATE
frames before the first drain — so the CI quick lane fails loudly, not
quietly.

  python benchmarks/autoscale_worker.py --nodes 4 --initial 2 [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pct(xs, q):
    import numpy as np
    return float(np.percentile(xs, q)) if xs else 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4,
                    help="pow2 capacity bucket (XLA device count)")
    ap.add_argument("--initial", type=int, default=2,
                    help="serving nodes at t=0 (also the drain floor)")
    ap.add_argument("--quick", action="store_true",
                    help="shorter phases for the CI smoke lane")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=4)
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
    from repro.runtime.autoscaler import Autoscaler, ServingSLO
    from repro.runtime.pool import PoolServer
    from repro.runtime.scheduler import PoolRouter, Request

    cfg = dataclasses.replace(
        get_arch("granite-3-2b"),
        n_layers=2, d_model=128, n_heads=8, n_kv_heads=4, d_ff=256,
        vocab_size=512)
    model = get_model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    # -- the seeded open-loop arrival trace ----------------------------------
    # (phase name, length in scheduler iterations, arrivals per iteration)
    if args.quick:
        phases = [("steady", 8, 0.4), ("burst", 8, 3.5),
                  ("cooldown", 30, 0.08)]
    else:
        phases = [("steady", 16, 0.4), ("burst", 12, 4.0),
                  ("cooldown", 50, 0.08)]
    rng = np.random.default_rng(args.seed)
    arrivals = []                       # (iteration, phase, rid, gen)
    base, rid = 0, 0
    for name, iters, rate in phases:
        t = float(base)
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= base + iters:
                break
            arrivals.append((t, name, rid, int(4 + rng.integers(0, 5))))
            rid += 1
        base += iters
    horizon_iters = base
    # the burst must exist even under an unlucky seed — the cell is
    # about the response to overload, not about sampling overload
    assert sum(1 for a in arrivals if a[1] == "burst") >= 3, \
        "seed produced no burst; pick another --seed"
    # one long-running straggler at the start of cooldown keeps live
    # pages on the pool while the drains fire — the warm path (device
    # page migration) is exercised, not just empty-node parks
    long_start = sum(i for _, i, _ in phases[:2])
    arrivals.append((float(long_start), "cooldown", rid, 40))
    arrivals.sort()
    prompts = {r: rng.integers(0, cfg.vocab_size, args.prompt_len,
                               dtype=np.int32)
               for _, _, r, _ in arrivals}

    # batch slots scale with the serving set: 3 per node, retuned live
    # after every membership change (the whole point of scaling up is
    # more concurrent decode, not just more KV room)
    SLOTS_PER_NODE = 3

    def fresh_router(server, pool):
        return PoolRouter(server, pool,
                          max_active=SLOTS_PER_NODE * args.initial,
                          horizon=4, prefill_chunk=2 * args.page_size)

    server = PoolServer(model, params, n_nodes=args.nodes,
                        active=args.initial, page_size=args.page_size,
                        hbm_pages_per_node=32, dtype=jnp.float32)
    pool = StoragePool(args.initial, heartbeat_timeout=1e9)
    pool.attach_server(server)

    # a maintenance drain mid-cooldown targets the node HOSTING the
    # long-running sequence — the autoscaler's own scale-downs pick the
    # emptiest node (warm path trivially), so the live-page migration
    # cost is exercised the way it occurs in production: an operator
    # retiring a loaded node while it is still decoding
    maint_iter = long_start + 6
    maint = {}

    def drive(router, asc=None, trace=None, maintenance=False):
        """Run an arrival trace open-loop; returns finished requests
        tagged with their arrival phase."""
        phase_of = {}
        queue = list(arrivals if trace is None else trace)
        it = 0
        # after the last request drains, keep the controller ticking
        # through a quiet grace period: burst-era samples age out of
        # the freshness window, the breach closes (the recovery stamp),
        # and sustained headroom walks the pool back down
        grace = (asc.window + asc.sustain + 3 * asc.cooldown + 8
                 if asc is not None else 0)
        while (queue or router.waiting or router.prefilling
               or router.active or grace > 0):
            busy = bool(queue or router.waiting or router.prefilling
                        or router.active)
            if not busy:
                grace -= 1
            while queue and queue[0][0] <= it:
                _, ph, r, gen = queue.pop(0)
                phase_of[r] = ph
                router.submit(Request(rid=r, prompt=prompts[r],
                                      max_tokens=gen))
            if asc is not None:
                asc.tick()
                router.max_active = \
                    SLOTS_PER_NODE * len(server.alive_nodes())
                if os.environ.get("ASC_DEBUG"):
                    m = asc.metrics()
                    print(f"it={it} alive={len(server.alive_nodes())} "
                          f"q={m['queue_depth']} p99={m['p99_ttft_s']:.3f} "
                          f"idle={asc._idle_ticks} "
                          f"breach={asc._breach_since is not None} "
                          f"act={len(router.active)} "
                          f"pre={len(router.prefilling)}",
                          file=sys.stderr)
            if maintenance and it >= maint_iter and not maint:
                # retire the most-loaded node while it still holds live
                # pages (retried each iteration until one qualifies)
                alive = server.alive_nodes()
                occ = {s: server.pages_per_node -
                       server.table.shard_free_pages(s) for s in alive}
                node = max(alive, key=lambda s: occ[s])
                if os.environ.get("ASC_DEBUG"):
                    print(f"maint-check it={it} occ={occ}",
                          file=sys.stderr)
                if occ[node] > 0 and len(alive) > args.initial:
                    mig_pre = pool.driver.stats.migrate_frames
                    rep = pool.drain_serving_node(node)
                    maint.update(
                        iteration=it, node=node,
                        victims=len(rep["victims"]),
                        migrated_pages=rep["migrated_pages"],
                        cold=len(rep["cold"]),
                        migrate_frames_before=mig_pre)
            _t0 = time.perf_counter()
            router.step()
            if os.environ.get("ASC_DEBUG"):
                print(f"it={it} step_dt="
                      f"{time.perf_counter() - _t0:.3f}", file=sys.stderr)
            it += 1
            if it > 40 * horizon_iters:
                raise RuntimeError("traffic never drained")
        return phase_of, it

    # -- calibration pass: fixed pool, full trace ----------------------------
    # Warms every jit bucket the elastic run will hit (admission chunks,
    # horizon steps, batch sizes) AND measures the steady-phase tail the
    # SLO is declared against — a target the initial serving set can
    # meet, which the burst will then breach.
    # trace every jit bucket the elastic run will hit — including the
    # peak-concurrency batch shapes the scaled-up pool admits
    cal0 = fresh_router(server, pool)
    cal0.max_active = SLOTS_PER_NODE * args.nodes
    drive(cal0)
    for s in list(server.sequence_ids()):
        server.free_sequence(s)
    # warm steady-only pass: the tail the SLO is declared against must
    # not be polluted by compile time
    cal_router = fresh_router(server, pool)
    drive(cal_router, trace=[a for a in arrivals if a[1] == "steady"])
    cal_ttft = [r.t_first - r.t_arrive for r in cal_router.finished]
    slo = ServingSLO(ttft_p99_s=max(4.0 * _pct(cal_ttft, 99), 0.05),
                     queue_depth=3)

    # -- rehearsal: the full elastic scenario, untimed -----------------------
    # The elastic run crosses memberships and kernels the fixed-pool
    # calibration never visits (intermediate serving sets, the
    # device-to-device migrate copy): one rehearsal traces them all so
    # compile time never lands in a measured percentile.
    for s in list(server.sequence_ids()):
        server.free_sequence(s)
    reh_router = fresh_router(server, pool)
    reh_asc = Autoscaler(reh_router, pool, slo=slo,
                         min_nodes=args.initial, max_nodes=args.nodes,
                         window=16, cooldown=2, headroom_frac=0.5,
                         sustain=3)
    drive(reh_router, reh_asc, maintenance=True)
    maint.clear()

    # -- the measured elastic run --------------------------------------------
    for s in list(server.sequence_ids()):
        server.free_sequence(s)
    pool.grow_serving(args.initial)
    while len(server.alive_nodes()) > args.initial:
        pool.drain_serving_node(server.alive_nodes()[-1])
    assert len(server.alive_nodes()) == args.initial
    router = fresh_router(server, pool)
    asc = Autoscaler(router, pool, slo=slo, min_nodes=args.initial,
                     max_nodes=args.nodes, window=16, cooldown=2,
                     headroom_frac=0.5, sustain=3)
    st = pool.driver.stats
    mig0, mbytes0 = st.migrate_frames, st.migrate_bytes
    t0 = time.perf_counter()
    phase_of, iters = drive(router, asc, maintenance=True)
    wall_s = time.perf_counter() - t0
    # drain back to the floor if the trace ended mid-episode (the
    # controller only ticks while traffic exists)
    while len(server.alive_nodes()) > args.initial:
        asc._idle_ticks, asc._last_action_tick = asc.sustain, -10 ** 9
        if asc.tick() is None:
            break

    # -- floors (CI quick lane gates on this process exiting 0) --------------
    ups = [d for d in asc.decisions if d.kind == "up"]
    downs = [d for d in asc.decisions if d.kind == "down"]
    assert ups, "burst never triggered a scale-up"
    assert downs, "sustained headroom never triggered a drain"
    assert not router.rejected, \
        f"shed {len(router.rejected)} requests — drains must be zero-drop"
    assert asc.recoveries, \
        "post-scale-up tail never recovered below the SLO"
    assert downs[0].tick > ups[0].tick, "drained before the burst grew"
    # MIGRATE frames appear exactly when a drain moves live pages: zero
    # while the pool was static, positive once the loaded node retired
    first_down = downs[0]
    assert maint, "maintenance drain never found a node to retire"
    assert maint["migrate_frames_before"] == mig0, \
        "MIGRATE frames on a static pool"
    assert maint["migrated_pages"] + maint["cold"] > 0, \
        f"maintenance drain moved nothing: {maint}"
    assert st.migrate_frames - mig0 == maint["migrated_pages"], \
        "MIGRATE counter out of step with the drain report"
    done = {r.rid for r in router.finished}
    assert done == set(prompts), f"lost requests: {set(prompts) - done}"

    per_phase = {}
    for name, _, rate in phases:
        reqs = [r for r in router.finished if phase_of[r.rid] == name]
        ttft = [r.t_first - r.t_arrive for r in reqs]
        tpot = [(r.t_done - r.t_first) / max(len(r.output) - 1, 1)
                for r in reqs]
        per_phase[name] = {
            "arrival_rate_per_iter": rate, "requests": len(reqs),
            "p50_ttft_s": _pct(ttft, 50), "p99_ttft_s": _pct(ttft, 99),
            "p50_tpot_s": _pct(tpot, 50), "p99_tpot_s": _pct(tpot, 99),
        }

    toks = sum(len(r.output) for r in router.finished)
    rec = {
        "nodes": args.nodes, "initial": args.initial,
        "quick": bool(args.quick), "seed": args.seed,
        "slo": {"ttft_p99_s": slo.ttft_p99_s,
                "queue_depth": slo.queue_depth},
        "requests": len(router.finished),
        "iterations": iters,
        "tokens_per_s": toks / wall_s,
        "phases": per_phase,
        "scale_events": [dataclasses.asdict(d) for d in asc.decisions],
        "recoveries": asc.recoveries,
        "slo_recovery_s": min(r["recovery_s"] for r in asc.recoveries),
        "peak_nodes": max(d.nodes for d in asc.decisions),
        "final_nodes": len(server.alive_nodes()),
        "rejected": len(router.rejected),
        "requeues": router.requeues,
        "migrate_frames": st.migrate_frames - mig0,
        "migrate_bytes": st.migrate_bytes - mbytes0,
        "migrated_pages_in": server.table.stats.migrated_in,
        "maintenance_drain": maint,
        "first_drain_tick": first_down.tick,
        "migration": A.migration_terms(
            type("S", (), {"migrate_frames": st.migrate_frames - mig0,
                           "migrate_bytes": st.migrate_bytes - mbytes0}),
            max(toks, 1)),
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
