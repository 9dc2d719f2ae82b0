"""Fused decode-horizon tests.

The contract: ``decode(horizon=H)`` — H tokens per host interaction,
on-device argmax/EOS/budget masking against horizon-reserved pages —
must produce greedy outputs token-for-token identical to the per-token
path, for any H, under eviction pressure, mid-horizon EOS, scheduler
joins/evicts at horizon boundaries, and pool failover.  Plus the
no-retrace guarantee: horizons over different active-sequence counts in
one pow2 bucket share a compiled program.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.kv_tier import PageStore, PageTableManager
from repro.models.api import get_model
from repro.runtime.pool import PoolServer
from repro.runtime.scheduler import ContinuousBatcher, Request
from repro.runtime.serve import PagedServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_model():
    cfg = dataclasses.replace(get_arch("granite_3_2b").reduced(),
                              n_layers=2, vocab_size=64)
    model = get_model(cfg, compute_dtype=jnp.float32, moe_no_drop=True)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# reserve_horizon / commit_horizon (table-manager unit level)
# ---------------------------------------------------------------------------


def _store(hbm_pages, page=4):
    return PageStore(n_layers=2, page_size=page, hbm_pages=hbm_pages,
                     n_kv_heads=2, head_dim=8, dtype=jnp.float32)


def test_reserve_horizon_pins_and_rollback_frees():
    t = PageTableManager(_store(16))
    t.add_sequence(0)
    t.set_length(0, 6)                      # 2 pages committed
    t.ensure_resident(0)
    phys = t.reserve_horizon(0, 9)          # covers 6+9=15 tokens -> 4 pages
    assert len(phys) == 4
    assert t.resident_pages == 4
    assert len(t._pinned) == 4              # whole reservation pinned
    # commit 3 of the horizon's 9: length 9 -> 3 pages; 1 page rolls back
    assert t.commit_horizon(0, 3) == 1
    assert t.length(0) == 9
    assert t.resident_pages == 3
    assert t.free_pages == 13
    t.unpin_all()
    # a full free still reclaims everything
    assert t.free_sequence(0) == 3
    assert t.free_pages == 16


def test_reserve_horizon_rejects_bad_horizon():
    t = PageTableManager(_store(8))
    t.add_sequence(0)
    with pytest.raises(ValueError, match="horizon"):
        t.reserve_horizon(0, 0)


def test_reserve_horizon_respects_pinned_working_set():
    """A reservation larger than the window must raise the same
    pinned-working-set error the per-token path raises (admission
    control's contract), not corrupt the table."""
    t = PageTableManager(_store(4))
    t.add_sequence(0)
    t.set_length(0, 4)
    with pytest.raises(RuntimeError, match="pinned working set"):
        t.reserve_horizon(0, 64)            # 17 pages > 4-page window
    t.unpin_all()


def test_failed_batch_reservation_rolls_back_earlier_seqs():
    """When one sequence of a horizon batch cannot reserve (window
    overflow), the sequences reserved before it must not keep phantom
    data-less pages resident — the plan rolls every reservation back."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(7)
    srv = PagedServer(model, params, page_size=4, hbm_pages=8,
                      dtype=jnp.float32)
    for i in range(2):
        srv.add_request(i, rng.integers(0, cfg.vocab_size, 5,
                                        dtype=np.int32))   # 2 pages each
    with pytest.raises(RuntimeError, match="pinned working set"):
        # 5+12 tokens -> 5 pages per seq; seq 1's reservation overflows
        srv._plan_horizon([0, 1], {0: 12, 1: 12})
    # residency back to the committed working set, nothing pinned
    assert srv.table.resident_pages == 4
    assert len(srv.table._pinned) == 0
    assert srv.table.host_pages == 0
    # the server stays serviceable: a fitting horizon decodes fine
    out = srv.decode(4, horizon=4)
    assert srv.table.length(0) == 5 + 4 and len(out[0]) == 4


# ---------------------------------------------------------------------------
# horizon equivalence: decode(horizon=H) == per-token path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("horizon", [1, 4, 17])
def test_decode_horizon_matches_per_token(horizon):
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 9, dtype=np.int32)
               for _ in range(3)]
    gen = 12

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                          dtype=jnp.float32)
        for i, p in enumerate(prompts):
            srv.add_request(i, p)
        out = srv.decode(gen, horizon=h)
        return out, srv

    ref, _ = run(None)
    got, srv = run(horizon)
    assert got == ref
    # the horizon reservation must be fully rolled back to the
    # committed lengths: same residency as the per-token run
    need = sum(srv.table.pages_needed(srv.table.length(s))
               for s in srv.sequence_ids())
    assert srv.table.resident_pages == need
    assert len(srv.table._pinned) == 0


def test_decode_horizon_kernel_path_matches_per_token():
    """The fused horizon with the Pallas kernel inside the scan — the
    TPU's path, interpreted here (CPU runs default to the jnp partial
    form) — emits exactly the per-token path's tokens."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 7, dtype=np.int32)
               for _ in range(2)]

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                          dtype=jnp.float32)
        srv._jnp_attention = False
        for i, p in enumerate(prompts):
            srv.add_request(i, p)
        return srv.decode(6, horizon=h)

    assert run(4) == run(None)


def test_decode_horizon_under_eviction_pressure():
    """Window smaller than the total working set: horizon decode of one
    sequence spills the other to the flash tier and back, outputs
    unchanged."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(2)
    B, S, gen = 2, 7, 4
    prompts = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)

    ref = PagedServer(model, params, page_size=4, hbm_pages=64,
                      dtype=jnp.float32)
    srv = PagedServer(model, params, page_size=4, hbm_pages=4,
                      dtype=jnp.float32)
    for i in range(B):
        la = ref.add_request(i, prompts[i])
        lb = srv.add_request(i, prompts[i])
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-4)
    o_ref1 = ref.decode(gen, seqs=[1])
    o_srv1 = srv.decode(gen, seqs=[1], horizon=4)    # seq 0 spills
    o_ref0 = ref.decode(gen, seqs=[0])
    o_srv0 = srv.decode(gen, seqs=[0], horizon=4)    # seq 0 pages back
    assert o_ref1 == o_srv1 and o_ref0 == o_srv0
    assert srv.tier_stats()["page_outs"] > 0
    assert srv.tier_stats()["page_ins"] > 0


def test_mid_horizon_eos_stops_on_device():
    """A sequence that emits EOS mid-horizon must stop appending/emitting
    on device; its tokens (including the EOS) match the per-token run,
    and the un-consumed reservation rolls back."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(2)]

    probe = PagedServer(model, params, page_size=4, hbm_pages=32,
                        dtype=jnp.float32)
    for i, p in enumerate(prompts):
        probe.add_request(i, p)
    free_run = probe.decode(8)
    eos = free_run[0][2]                    # seq 0's third decode token

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                          dtype=jnp.float32)
        for i, p in enumerate(prompts):
            srv.add_request(i, p)
        out = srv.decode(8, horizon=h, eos_id=int(eos))
        return out, srv

    # per-token semantics of eos_id via the horizon path with H=1
    ref, _ = run(1)
    got, srv = run(8)                       # EOS lands mid-horizon
    assert got == ref
    for s, toks in got.items():
        cut = free_run[s]
        if int(eos) in cut:
            k = cut.index(int(eos))
            assert toks == cut[:k + 1]      # stops right after EOS
        else:
            assert toks == cut
    # committed lengths reflect only the consumed part of the horizon
    assert srv.table.length(0) == 6 + len(got[0])
    assert len(srv.table._pinned) == 0


def test_horizon_budgets_stop_per_sequence():
    """Per-sequence budgets (the scheduler's max_tokens enforcement)
    mask on device: each sequence stops at its own budget inside one
    fused horizon."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 5, dtype=np.int32)
               for _ in range(3)]
    srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                      dtype=jnp.float32)
    for i, p in enumerate(prompts):
        srv.add_request(i, p)
    ref = srv.decode(8, horizon=None)       # consumes pending; re-serve
    srv2 = PagedServer(model, params, page_size=4, hbm_pages=32,
                       dtype=jnp.float32)
    for i, p in enumerate(prompts):
        srv2.add_request(i, p)
    budgets = {0: 2, 1: 8, 2: 5}
    got = srv2.decode(8, horizon=8, budgets=budgets)
    for s in range(3):
        assert got[s] == ref[s][:budgets[s]], s
        assert srv2.table.length(s) == 5 + budgets[s]


def test_per_token_path_honors_eos_and_budgets():
    """eos_id/budgets must stop sequences on the per-token path exactly
    like the fused path (host-side between steps vs on device)."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(2)]

    probe = PagedServer(model, params, page_size=4, hbm_pages=32,
                        dtype=jnp.float32)
    for i, p in enumerate(prompts):
        probe.add_request(i, p)
    eos = int(probe.decode(6)[0][2])

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                          dtype=jnp.float32)
        for i, p in enumerate(prompts):
            srv.add_request(i, p)
        out = srv.decode(6, horizon=h, eos_id=eos, budgets={0: 6, 1: 3})
        return out, {s: srv.table.length(s) for s in (0, 1)}

    out_pt, len_pt = run(None)
    out_h, len_h = run(4)
    assert out_pt == out_h
    assert len_pt == len_h                  # identical commit trajectory
    assert len(out_pt[1]) == 3              # budget respected


# ---------------------------------------------------------------------------
# no-retrace: one compiled program per (pow2 batch, pow2 pps, pow2 H)
# ---------------------------------------------------------------------------


def test_horizon_no_retrace_across_active_counts():
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(3)
    srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                      dtype=jnp.float32)
    if not hasattr(srv._horizon_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this jax")
    for i in range(4):
        srv.add_request(i, rng.integers(0, cfg.vocab_size, 5,
                                        dtype=np.int32))
    srv.decode(4, seqs=[0, 1, 2], horizon=4)
    sig0 = srv._horizon_jit._cache_size()
    srv.decode(4, seqs=[0, 1, 2, 3], horizon=4)   # same pow2 bucket (4)
    assert srv._horizon_jit._cache_size() == sig0
    # a horizon tail in the same pow2 bucket keeps the program too:
    # decode(6, horizon=4) runs fused chunks H=4 then H=2
    srv.decode(6, seqs=[0, 1], horizon=4)
    sig1 = srv._horizon_jit._cache_size()
    srv.decode(6, seqs=[0, 1], horizon=4)
    assert srv._horizon_jit._cache_size() == sig1


# ---------------------------------------------------------------------------
# scheduler on horizon boundaries
# ---------------------------------------------------------------------------


def test_batcher_horizon_matches_per_token_schedule():
    """ContinuousBatcher(horizon=H) — joins/evicts at horizon
    boundaries, device-side EOS + budgets — must finish every request
    with output identical to the per-token schedule."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(4)]
    gens = [3, 7, 2, 5]

    probe = PagedServer(model, params, page_size=4, hbm_pages=64,
                        dtype=jnp.float32)
    probe.add_request(0, prompts[0])
    eos = int(probe.decode(4)[0][1])        # a token that really occurs

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=16,
                          dtype=jnp.float32)
        b = ContinuousBatcher(srv, max_active=2, horizon=h)
        for i, (p, g) in enumerate(zip(prompts, gens)):
            b.submit(Request(rid=i, prompt=p, max_tokens=g, eos_id=eos))
        stats = b.run_to_completion()
        assert stats["requests"] == 4
        assert srv.table.free_pages == srv.hbm_pages   # all reclaimed
        return {r.rid: r.output for r in b.finished}

    ref = run(1)
    for h in (3, 4, 8):
        assert run(h) == ref, h


def test_batcher_horizon_mixed_eos_truncates_host_side():
    """Active requests with different eos ids cannot share one device
    eos mask; the batcher truncates host-side and outputs still match
    the per-token schedule."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(2)]

    probe = PagedServer(model, params, page_size=4, hbm_pages=64,
                        dtype=jnp.float32)
    for i, p in enumerate(prompts):
        probe.add_request(i, p)
    free_run = probe.decode(6)
    eos_ids = [int(free_run[0][1]), int(free_run[1][2])]

    def run(h):
        srv = PagedServer(model, params, page_size=4, hbm_pages=32,
                          dtype=jnp.float32)
        b = ContinuousBatcher(srv, max_active=2, horizon=h)
        for i, p in enumerate(prompts):
            b.submit(Request(rid=i, prompt=p, max_tokens=6,
                             eos_id=eos_ids[i]))
        b.run_to_completion()
        return {r.rid: r.output for r in b.finished}

    assert run(4) == run(1)


# ---------------------------------------------------------------------------
# pool: sharded horizon + failover at a horizon boundary (slow lane)
# ---------------------------------------------------------------------------


def test_pool_horizon_one_node_matches_paged():
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 9, dtype=np.int32)
               for _ in range(3)]
    ref = PagedServer(model, params, page_size=4, hbm_pages=32,
                      dtype=jnp.float32)
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=32, dtype=jnp.float32)
    for i, p in enumerate(prompts):
        ref.add_request(i, p)
        srv.add_request(i, p)
    assert srv.decode(8, horizon=4) == ref.decode(8)


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_pool_failover_mid_horizon_decode():
    """Kill a node while a horizon-scheduled router is mid-flight: the
    victims requeue at the next horizon boundary, re-prefill
    prompt+history on survivors, and finish with outputs identical to
    the uninterrupted per-token run."""
    stdout = _run("""
    import dataclasses, numpy as np
    import jax, jax.numpy as jnp
    from repro.configs.base import get_arch
    from repro.core.storage_pool import StoragePool
    from repro.models.api import get_model
    from repro.runtime.pool import PoolServer
    from repro.runtime.scheduler import PoolRouter, Request
    from repro.runtime.serve import PagedServer

    cfg = dataclasses.replace(get_arch("granite_3_2b").reduced(),
                              n_layers=2, vocab_size=64)
    model = get_model(cfg, compute_dtype=jnp.float32, moe_no_drop=True)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(5)]
    gens = [9, 11, 8, 10, 9]

    ref = PagedServer(model, params, page_size=4, hbm_pages=64,
                      dtype=jnp.float32)
    ref_out = {}
    for i, p in enumerate(prompts):
        ref_out[i] = [int(np.argmax(np.asarray(ref.add_request(i, p))))]
    for i, toks in ref.decode(max(gens) - 1).items():
        ref_out[i] += toks
    ref_out = {i: o[:g] for (i, o), g in zip(ref_out.items(), gens)}

    srv = PoolServer(model, params, n_nodes=4, page_size=4,
                     hbm_pages_per_node=8, dtype=jnp.float32)
    pool = StoragePool(4, heartbeat_timeout=0.0)
    pool.attach_server(srv)
    router = PoolRouter(srv, pool, max_active=5, horizon=4)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        router.submit(Request(rid=i, prompt=p, max_tokens=g))
    router.step()                        # one fused horizon everywhere
    victim = srv.node_of(0)
    assert any(len(r.output) > 1 for r in router.active.values())
    pool.nodes[pool.serving_ips()[victim]].fail()     # dies mid-decode
    router.run_to_completion()
    assert router.requeues >= 1
    assert victim not in srv.alive_nodes()
    by_id = {r.rid: r.output for r in router.finished}
    for i, g in enumerate(gens):
        assert by_id[i] == ref_out[i], (i, by_id[i], ref_out[i])
    print("HORIZON_FAILOVER_OK")
    """)
    assert "HORIZON_FAILOVER_OK" in stdout
