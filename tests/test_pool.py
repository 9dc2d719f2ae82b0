"""Pool-sharded serving tests.

Fast lane (single device): per-shard page accounting in
PageTableManager, the NodeSpec aliasing fix, the 1-node PoolServer vs
PagedServer equivalence (the shard_map path itself), and the frontend
control-plane wiring.  Slow lane (subprocess with forced host devices):
multi-node decode equivalence to 1e-4, mid-decode failover, and the
aggregate-equals-sum-of-nodes telemetry invariant on a real pool run.
"""
import dataclasses
import json
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
from repro.core.storage_pool import DockerSSDNode, NodeSpec, StoragePool
from repro.models.api import get_model
from repro.runtime.pool import PoolServer
from repro.runtime.scheduler import PoolRouter, Request
from repro.runtime.serve import PagedServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_model():
    cfg = dataclasses.replace(get_arch("granite_3_2b").reduced(),
                              n_layers=2, vocab_size=64)
    model = get_model(cfg, compute_dtype=jnp.float32, moe_no_drop=True)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# satellites: NodeSpec aliasing, sharded page accounting
# ---------------------------------------------------------------------------


def test_nodespec_default_not_shared():
    """Every node must own its spec: mutating one node's spec (e.g. a
    degraded channel count) cannot leak into the rest of the pool."""
    a = DockerSSDNode("10.0.1.2")
    b = DockerSSDNode("10.0.1.3")
    a.spec.channels = 1
    assert b.spec.channels == NodeSpec().channels
    pool = StoragePool(3)
    ips = list(pool.nodes)
    pool.nodes[ips[0]].spec.channels = 2
    assert pool.nodes[ips[1]].spec.channels == NodeSpec().channels
    # an explicitly passed spec is copied per node, not aliased
    pool2 = StoragePool(2, spec=NodeSpec(channels=7))
    n0, n1 = pool2.nodes.values()
    assert n0.spec is not n1.spec and n0.spec.channels == 7
    pool2.scale_to(3)
    assert list(pool2.nodes.values())[2].spec.channels == NodeSpec().channels


def _store(hbm_pages, n_layers=2, page=4):
    return PageStore(n_layers=n_layers, page_size=page, hbm_pages=hbm_pages,
                     n_kv_heads=2, head_dim=8, dtype=jnp.float32)


def test_sharded_alloc_stays_in_shard():
    """Striped placement: logical page i of a sequence lands in shard
    i % n_shards, and every physical id falls inside its shard's
    contiguous window."""
    t = PageTableManager(_store(16), n_shards=4)
    t.add_sequence(0)
    phys = t.ensure_resident(0, n_tokens=5 * 4)     # 5 logical pages
    for pi, p in enumerate(phys):
        assert t.shard_of_phys(p) == pi % 4
    assert t.free_pages == 11
    assert t.shard_free_pages(0) == 2               # pages 0 and 4 placed
    assert t.free_sequence(0) == 5
    assert t.free_pages == 16


def test_shard_eviction_is_local_and_counted_per_shard():
    """Eviction never crosses a node boundary (each DockerSSD tiers
    against its own flash) and every counter lands on the right shard —
    the pool aggregate is the field-wise sum of the nodes."""
    placement = {}
    t = PageTableManager(_store(8), n_shards=2,
                         shard_of=lambda seq, pi: placement[seq])
    for s in range(4):
        placement[s] = s % 2
        t.add_sequence(s)
    # fill both 4-page windows, then overflow shard 0 only
    for s in (0, 1):
        t.ensure_resident(s, n_tokens=16)           # 4 pages each
    t.ensure_resident(2, n_tokens=8)                # 2 pages in shard 0
    assert t.stats.page_outs == 2
    assert [ss.page_outs for ss in t.shard_stats] == [2, 0]
    # the spilled pages belong to shard 0's host tier
    assert all(placement[k[0]] == 0 for k in t._host)
    # paging seq 0 back in evicts within shard 0; shard 1 untouched
    t.ensure_resident(0, n_tokens=16)
    assert t.shard_stats[1].page_outs == t.shard_stats[1].page_ins == 0
    agg = vars(t.stats)
    per = [vars(ss) for ss in t.shard_stats]
    assert all(agg[k] == sum(p[k] for p in per) for k in agg)


def test_dead_shard_rejects_allocation():
    t = PageTableManager(_store(8), n_shards=2)
    t.add_sequence(0)
    t.ensure_resident(0, n_tokens=8)                # pages on both shards
    assert t.sequences_on_shard(1) == {0}
    t.disable_shard(1)
    t.add_sequence(1)
    with pytest.raises(RuntimeError, match="dead"):
        t.ensure_resident(1, n_tokens=8)            # page 1 -> shard 1


# ---------------------------------------------------------------------------
# PoolServer on one device: the shard_map path itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["placed", "striped"])
def test_pool_server_one_node_matches_paged(policy):
    """A 1-node pool must reproduce PagedServer exactly: same prefill
    logits (1e-4), same greedy tokens — the ownership masking and the
    LSE partial merge are the identity on one shard."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(0)
    B, S, gen = 3, 9, 5
    prompts = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    ref = PagedServer(model, params, page_size=4, hbm_pages=32,
                      dtype=jnp.float32)
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=32, dtype=jnp.float32,
                     policy=policy)
    for i in range(B):
        la = ref.add_request(i, prompts[i])
        lb = srv.add_request(i, prompts[i])
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-4)
    assert ref.decode(gen) == srv.decode(gen)
    agg = srv.tier_stats()
    per = srv.node_tier_stats()
    assert len(per) == 1
    assert all(agg[k] == per[0][k] for k in per[0])


def test_pool_kernel_attention_matches_paged_server():
    """On the kernel path — the TPU's, run interpreted here — a 1-node
    pool reproduces PagedServer bit for bit: every page is owned by the
    one node, so the ``(o, m, l)`` merge is an exact identity on the
    kernel's own output (prefill logits, per-token and horizon tokens)."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(4)
    B, S, gen = 2, 9, 6
    prompts = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    ref = PagedServer(model, params, page_size=4, hbm_pages=32,
                      dtype=jnp.float32)
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=32, dtype=jnp.float32)
    ref._jnp_attention = srv._jnp_attention = False
    for i in range(B):
        np.testing.assert_array_equal(
            np.asarray(ref.add_request(i, prompts[i])),
            np.asarray(srv.add_request(i, prompts[i])))
    assert ref.decode(2) == srv.decode(2)
    assert ref.decode(gen, horizon=4) == srv.decode(gen, horizon=4)


def test_pool_router_frontend_control_plane():
    """End-to-end on one node: requests flow frontend -> Ether-oN frame
    -> placement -> sharded decode; place/free control frames are
    cost-accounted and logged at the node."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 6, dtype=np.int32)
               for _ in range(3)]
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=32, dtype=jnp.float32)
    pool = StoragePool(1)
    pool.attach_server(srv)
    router = PoolRouter(srv, pool, max_active=2)
    for i, p in enumerate(prompts):
        router.submit(Request(rid=i, prompt=p, max_tokens=3))
    stats = router.run_to_completion()
    assert stats["requests"] == 3
    node = pool.nodes[pool.serving_ips()[0]]
    places = [e for e in node.serving_log if e[0] == "place"]
    frees = [e for e in node.serving_log if e[0] == "free"]
    assert len(places) == 3 and len(frees) == 3
    assert pool.driver.stats.control_frames == 6
    assert srv.table.free_pages == srv.hbm_pages     # everything reclaimed


def test_pool_server_eviction_under_pressure():
    """Per-node window smaller than the working set: the pool path must
    stay correct while pages spill to the node's flash tier."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(2)
    B, S, gen = 2, 7, 4
    prompts = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    ref = PagedServer(model, params, page_size=4, hbm_pages=64,
                      dtype=jnp.float32)
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=4, dtype=jnp.float32)
    for i in range(B):
        la = ref.add_request(i, prompts[i])
        lb = srv.add_request(i, prompts[i])
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-4)
    o_ref1 = ref.decode(gen, seqs=[1])
    o_srv1 = srv.decode(gen, seqs=[1])               # seq 0 spills
    o_ref0 = ref.decode(gen, seqs=[0])
    o_srv0 = srv.decode(gen, seqs=[0])               # seq 0 pages back in
    assert o_ref1 == o_srv1 and o_ref0 == o_srv0
    assert srv.tier_stats()["page_outs"] > 0
    assert srv.tier_stats()["page_ins"] > 0


def test_striped_pool_fails_fast_on_node_loss():
    """A striped extent spans every node, so a node failure cannot be
    failed over: the router must raise a clear error instead of
    requeueing work that can never re-admit."""
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(4)
    srv = PoolServer(model, params, n_nodes=1, page_size=4,
                     hbm_pages_per_node=16, dtype=jnp.float32,
                     policy="striped")
    pool = StoragePool(1, heartbeat_timeout=0.0)
    pool.attach_server(srv)
    router = PoolRouter(srv, pool, max_active=2)
    router.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, 6, dtype=np.int32), max_tokens=4))
    router.step()
    pool.nodes[pool.serving_ips()[0]].fail()
    with pytest.raises(RuntimeError, match="striped pool lost node"):
        router.run_to_completion()


# ---------------------------------------------------------------------------
# multi-node semantics (subprocess with forced host devices)
# ---------------------------------------------------------------------------

def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_SETUP = """
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
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
    gens = [4, 6, 3, 5, 4]

    ref = PagedServer(model, params, page_size=4, hbm_pages=64,
                      dtype=jnp.float32)
    ref_logits = [np.asarray(ref.add_request(i, p))
                  for i, p in enumerate(prompts)]
    ref_out = {i: [int(np.argmax(l))] for i, l in enumerate(ref_logits)}
    for i, toks in ref.decode(max(gens) - 1).items():
        ref_out[i] += toks
    ref_out = {i: o[:g] for (i, o), g in zip(ref_out.items(), gens)}
"""


@pytest.mark.slow
def test_multinode_decode_matches_single_node():
    """4-node pool, both placement policies: prefill logits within 1e-4
    of the 1-node PagedServer and identical greedy decode."""
    stdout = _run(_SETUP + """
    for policy in ("placed", "striped"):
        srv = PoolServer(model, params, n_nodes=4, page_size=4,
                         hbm_pages_per_node=8, dtype=jnp.float32,
                         policy=policy)
        for i, p in enumerate(prompts):
            lb = np.asarray(srv.add_request(i, p))
            assert np.max(np.abs(lb - ref_logits[i])) < 1e-4, policy
        out = srv.decode(max(gens))
        for i, g in enumerate(gens):
            assert out[i][:g - 1] == ref_out[i][1:], (policy, i)
        if policy == "placed":
            assert len({srv.node_of(i) for i in range(5)}) > 1
    print("MULTINODE_OK")
    """)
    assert "MULTINODE_OK" in stdout


@pytest.mark.slow
def test_failover_requeues_and_completes():
    """Kill a node mid-decode: its sequences requeue through the router,
    finish on the survivors, and the final outputs equal the
    uninterrupted single-node run."""
    stdout = _run(_SETUP + """
    srv = PoolServer(model, params, n_nodes=4, page_size=4,
                     hbm_pages_per_node=8, dtype=jnp.float32)
    pool = StoragePool(4, heartbeat_timeout=0.0)
    pool.attach_server(srv)
    router = PoolRouter(srv, pool, max_active=5)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        router.submit(Request(rid=i, prompt=p, max_tokens=g))
    router.step(); router.step()
    victim = srv.node_of(0)
    pool.nodes[pool.serving_ips()[victim]].fail()
    router.run_to_completion()
    assert router.requeues >= 1
    assert victim not in srv.alive_nodes()
    assert any(e[0] == "serve-requeue" for e in pool.events)
    by_id = {r.rid: r.output for r in router.finished}
    for i, g in enumerate(gens):
        assert by_id[i] == ref_out[i], (i, by_id[i], ref_out[i])
    print("FAILOVER_OK")
    """)
    assert "FAILOVER_OK" in stdout


@pytest.mark.slow
def test_aggregate_tier_stats_is_sum_of_nodes():
    """On a real multi-node run with spill pressure, the pool aggregate
    telemetry equals the field-wise sum of the per-node stats."""
    stdout = _run(_SETUP + """
    srv = PoolServer(model, params, n_nodes=2, page_size=4,
                     hbm_pages_per_node=4, dtype=jnp.float32)
    pool = StoragePool(2)
    pool.attach_server(srv)
    # two sequences per node-sized window: decoding one at a time forces
    # per-node eviction traffic
    for i, p in enumerate(prompts[:4]):
        node = pool.place_sequence(i, 6 + 4)
        srv.add_request(i, p, node=node)
    for i in range(4):
        srv.decode(3, seqs=[i])
    agg = srv.tier_stats()
    per = srv.node_tier_stats()
    assert agg["page_outs"] > 0
    assert all(agg[k] == sum(p[k] for p in per) for k in per[0]), \\
        (agg, per)
    served = pool.serving_tier_stats()
    assert served["pool"] == agg and served["nodes"] == per
    print("STATS_OK")
    """)
    assert "STATS_OK" in stdout