"""Pool-sharded serving — the distributed decode path over DockerSSDs.

``PoolServer`` turns the single-device :class:`~repro.runtime.serve.
PagedServer` into one distributed system spanning the storage pool
(the paper's preferred offloading mode, Fig 8b): the jitted decode /
prefill steps are ``shard_map``-ped over a device mesh whose ``model``
axis is the pool — **shard i's slice of the PageStore pages axis is
DockerSSD node i's HBM window** (``runtime/sharding.pool_store_spec``).
One jitted step per token serves every sequence in the pool, wherever
its pages live.

Placement policies (``PageTableManager.shard_of``):

  * ``"placed"`` — each sequence's extent lives wholly on one node,
    chosen least-loaded by the pool frontend (StoragePool routes the
    admission over Ether-oN control frames).  Node failure only costs
    that node's sequences; the router re-prefills them elsewhere.
  * ``"striped"`` — a sequence's logical pages stripe round-robin
    across all nodes (the D-Cache sequence-sharded extent of
    DESIGN.md / runtime/sharding.cache_spec_shardings).  Maximum
    bandwidth for one long context; a node failure costs the pool.

Both run through the same device program, because the decode body is
ownership-driven: every node computes q/k/v for the new tokens (each
DockerSSD stores the full model in its flash), the owner of the tail
page appends via a masked scatter, every node runs paged attention over
*its own* pages only, and the per-node online-softmax partials
``(acc, m, l)`` are merged exactly with one ``pmax`` + two ``psum``
log-sum-exp collectives.  Control traffic (admission / placement /
free) rides Ether-oN frames; only these collectives ride the jax mesh —
the split DESIGN.md §Pool serving documents.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.kv_tier import PageStore, PageTableManager
from repro.runtime import sharding as shd
from repro.runtime.serve import PagedServer, combine_partials

POOL_AXIS = "model"


class PoolServer(PagedServer):
    """Mesh-sharded tiered-KV serving across the storage pool.

    Same public surface as :class:`PagedServer` (the router and the
    StoragePool frontend talk to it identically) plus the pool surface:
    per-node capacity (``node_free_pages``), placement
    (``least_loaded_node``, ``add_request(..., node=)``), failure
    (``fail_node``) and per-node telemetry (``node_tier_stats``).

    The page-table manager allocates per shard (each node tiers against
    its own window and flash), the store's pages axis is laid out over
    the mesh, and the jitted steps are built by shard_mapping the
    ownership-aware bodies below with ``pool_step_specs``.
    """

    def __init__(self, model, params, *, n_nodes: Optional[int] = None,
                 active: Optional[int] = None,
                 mesh: Optional[Mesh] = None, page_size: int = 16,
                 hbm_pages_per_node: int = 32, dtype=jnp.float32,
                 policy: str = "placed", prefix_cache: bool = True,
                 page_dtype: str = "fp32",
                 hbm_bytes_per_node: Optional[int] = None):
        if policy not in ("placed", "striped"):
            raise ValueError(f"unknown placement policy {policy!r}")
        if active is not None and policy != "placed":
            raise ValueError(
                "elastic pools (active=) need the placed policy — a "
                "striped extent spans every node by construction, so "
                "membership cannot change under it")
        if mesh is None:
            n = n_nodes if n_nodes is not None else len(jax.devices())
            if active is not None:
                # elastic capacity compiles against the pow2 mesh
                # bucket: membership changes inside the bucket reuse
                # every compiled program (zero retrace), growing past
                # it means provisioning a new server
                n = shd.mesh_bucket(n)
            mesh = shd.pool_mesh(n)
        if POOL_AXIS not in mesh.axis_names:
            raise ValueError(f"pool mesh needs a {POOL_AXIS!r} axis")
        self.mesh = mesh
        self.n_nodes = int(mesh.shape[POOL_AXIS])
        if active is not None and not (1 <= active <= self.n_nodes):
            raise ValueError(f"active={active} must be in "
                             f"[1, {self.n_nodes}]")
        # elastic membership: shards beyond the initially-active count
        # start parked — their windows exist (the mesh and store are
        # sized for the full bucket) but placement skips them until a
        # join activates them
        self._parked: set = (set(range(active, self.n_nodes))
                             if active is not None else set())
        if hbm_bytes_per_node is not None:
            # per-node byte budget -> dtype-aware page count (same
            # capacity knob as PagedServer's hbm_bytes, per DockerSSD)
            pb = PageStore.stacked_page_bytes(
                n_layers=model.cfg.n_layers, page_size=page_size,
                n_kv_heads=model.cfg.n_kv_heads, head_dim=model.cfg.hd,
                dtype=dtype, page_dtype=page_dtype)
            hbm_pages_per_node = max(1, int(hbm_bytes_per_node) // pb)
        self.pages_per_node = hbm_pages_per_node
        self.policy = policy
        # every DockerSSD holds the whole model: replicate the weights
        # over the mesh once, not on every step
        params = jax.device_put(params, NamedSharding(mesh, P()))
        self._placement: Dict[int, int] = {}
        self._dead: set = set()
        super().__init__(model, params, page_size=page_size,
                         hbm_pages=self.n_nodes * hbm_pages_per_node,
                         dtype=dtype, prefix_cache=prefix_cache,
                         page_dtype=page_dtype)
        in_specs, out_specs = shd.pool_step_specs(self.quantized)
        self._sharded_decode = jax.shard_map(
            self._decode_body, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)
        chunk_in, chunk_out = shd.pool_chunk_specs(self.quantized)
        self._sharded_chunk = jax.shard_map(
            self._chunk_body, mesh=mesh, in_specs=chunk_in,
            out_specs=chunk_out, check_vma=False)
        # shard_map'd horizon / speculative bodies, one per (static)
        # horizon length — bounded by the pow2 bucketing in
        # ``horizon_batch`` / ``spec_horizon_batch``
        self._sharded_horizons: Dict[int, object] = {}
        self._sharded_specs: Dict[int, object] = {}

    # -- store / table factories ---------------------------------------------

    def _new_store(self) -> PageStore:
        store = super()._new_store()
        store.place({k: NamedSharding(self.mesh, s) for k, s in
                     shd.pool_state_spec(store.quantized).items()})
        return store

    def _new_table(self) -> PageTableManager:
        table = PageTableManager(self.store, n_shards=self.n_nodes,
                                 shard_of=self._shard_of)
        for s in self._dead:
            table.disable_shard(s)
        for s in self._parked:
            table.park_shard(s)
        return table

    def _shard_of(self, seq_id: int, page_idx: int) -> int:
        if self.policy == "placed":
            return self._placement[seq_id]
        return page_idx % self.n_nodes

    # -- pool placement surface ----------------------------------------------

    def alive_nodes(self) -> List[int]:
        """Nodes placement may target: not failed, not parked."""
        return [s for s in range(self.n_nodes)
                if s not in self._dead and s not in self._parked]

    def parked_nodes(self) -> List[int]:
        return sorted(self._parked)

    @property
    def active_count(self) -> int:
        return len(self.alive_nodes())

    def node_free_pages(self) -> List[int]:
        return [self.table.shard_free_pages(s) for s in range(self.n_nodes)]

    def least_loaded_node(self) -> int:
        alive = self.alive_nodes()
        if not alive:
            raise RuntimeError("no alive pool nodes")
        return max(alive, key=lambda s: (self.table.shard_free_pages(s), -s))

    def best_prefix_node(self, prompt):
        """(node, tokens): the alive node whose per-shard prefix index
        covers the longest prefix of ``prompt`` — the placement signal
        that routes a request to where its prefix KV already lives
        (placed policy; a striped extent matches per page across every
        node by construction).  (None, 0) when nothing matches."""
        best, best_n = None, 0
        for s in self.alive_nodes():
            n = self.table.prefix_tokens_on_shard(prompt, s)
            if n > best_n:
                best, best_n = s, n
        return best, best_n

    def pick_prefix_node(self, prompt, n_tokens: Optional[int] = None):
        """THE prefix-placement policy (one copy — the StoragePool
        frontend and direct ``begin_request`` both route through it):
        the prefix-owning node wins only while its window has room for
        the request's whole ``n_tokens`` extent (default: the prompt —
        conservative, since shares need no new pages, but the fallback
        must never wedge an admission).  None -> caller falls back to
        least-loaded."""
        node, hit = self.best_prefix_node(prompt)
        if not hit:
            return None
        need = self.pages_needed(n_tokens if n_tokens is not None
                                 else len(prompt))
        if self.table.shard_free_pages(node) < need:
            return None
        return node

    def begin_request(self, seq_id: int, prompt, *,
                      node: Optional[int] = None) -> int:
        """Open an admission onto the pool.  ``node`` pins the placement
        (the StoragePool frontend routes it there); default prefers the
        node already holding the prompt's prefix, else least-loaded.
        Striped policy ignores ``node`` — the extent spans every node by
        construction."""
        if self.policy == "placed" and seq_id not in self._placement:
            if node is None:
                node = self.pick_prefix_node(prompt)
            target = self.least_loaded_node() if node is None else int(node)
            if target in self._dead:
                raise RuntimeError(f"node {target} is dead")
            self._placement[seq_id] = target
        try:
            return super().begin_request(seq_id, prompt)
        except Exception:
            self._placement.pop(seq_id, None)
            raise

    def add_request(self, seq_id: int, prompt, *,
                    node: Optional[int] = None,
                    chunk: Optional[int] = None):
        """Blocking admission: placement + cached-prefix match + chunked
        prefill of the uncached suffix (see PagedServer.add_request)."""
        self.begin_request(seq_id, prompt, node=node)
        logits = None
        while logits is None:
            logits = self.prefill_chunk(seq_id, chunk)
        return logits

    def free_sequence(self, seq_id: int) -> int:
        freed = super().free_sequence(seq_id)
        self._placement.pop(seq_id, None)
        return freed

    def node_of(self, seq_id: int) -> Optional[int]:
        return self._placement.get(seq_id)

    def fail_node(self, node: int) -> List[int]:
        """Simulated DockerSSD failure: the node's HBM window and flash
        tier are gone.  Every sequence with pages homed there is dropped
        (its ids are returned so the router can re-prefill them on the
        survivors) and the shard is taken out of allocation."""
        victims = set(self.table.sequences_on_shard(node))
        # an admission opened here whose first chunk hasn't allocated
        # pages yet is homed here too (placement is recorded at
        # begin_request, pages only at the first prefill chunk) — it
        # must requeue with the rest, not prefill onto a dead shard
        victims |= {s for s, n in self._placement.items() if n == node}
        victims = sorted(victims)
        self._dead.add(node)
        self._parked.discard(node)
        for s in victims:
            self.free_sequence(s)
        self.table.disable_shard(node)
        return victims

    # -- elastic membership (join / drain) ------------------------------------

    def activate_node(self, node: int):
        """Join a parked node into the serving set.  Zero retrace: the
        shard_map programs were compiled once against the full pow2
        mesh bucket, and an inactive shard simply owned no pages (its
        attention partials are the LSE identity), so activation is pure
        host-side bookkeeping — the very next decode step may place
        pages there."""
        if node in self._dead:
            raise RuntimeError(
                f"node {node} is dead (window lost); a failed node "
                "cannot rejoin the serving set")
        if not (0 <= node < self.n_nodes):
            raise ValueError(f"node {node} outside mesh bucket "
                             f"[0, {self.n_nodes})")
        self._parked.discard(node)
        self.table.unpark_shard(node)

    def _drain_dst(self, need: int, exclude: int) -> Optional[int]:
        """Pick the warm-migration destination: the least-loaded alive
        node (excluding the drainee) whose window has room for ``need``
        pages.  None -> the caller takes the cold path."""
        cand = [s for s in self.alive_nodes() if s != exclude]
        if not cand:
            return None
        best = max(cand, key=lambda s: (self.table.shard_free_pages(s), -s))
        return best if self.table.shard_free_pages(best) >= need else None

    def drain_node(self, node: int, on_migrate=None) -> Dict:
        """Two-path zero-drop drain: remove ``node`` from the serving
        set while every request keeps decoding.

        Warm path (preferred): each victim sequence's resident pages
        move device-to-device onto a surviving node's window
        (``PageTableManager.migrate_page`` — exact bytes, so sampling
        streams and logits are untouched and outputs stay
        token-identical).  ``on_migrate(seq_id, page_idx, src, dst)``
        fires per moved page — the StoragePool frontend announces each
        one with a MIGRATE frame for cost accounting.

        Cold path (fallback): a victim whose pages don't fit anywhere
        (or whose destination dies mid-migration) is freed and reported
        in ``cold`` — the caller requeues it through the PR-2 failover
        machinery, which teacher-forces the already-emitted tokens, so
        outputs stay token-identical there too.

        Shared prefix pages migrate once; every sharer's mapping
        follows the copy.  A sharer later re-placed elsewhere keeps
        reading the moved page — the merged attention is
        ownership-agnostic, so only *new* appends land on the sharer's
        own node.  Runs between scheduler steps (no pages pinned).
        """
        if self.policy != "placed":
            raise RuntimeError("striped pools cannot drain a node — the "
                               "extent spans every node by construction")
        if node in self._dead:
            raise RuntimeError(f"node {node} is dead; drain is for "
                               "planned removal of a live node")
        if len(self.alive_nodes()) <= 1:
            raise RuntimeError("cannot drain the last active node")
        # park first so concurrent placement and destination picking
        # exclude the drainee
        self._parked.add(node)
        self.table.park_shard(node)
        victims = set(self.table.sequences_on_shard(node))
        victims |= {s for s, n in self._placement.items() if n == node}
        victims = sorted(victims)
        migrated, cold, moved = 0, [], {}
        for seq in victims:
            try:
                res = self.table.resident_on_shard(seq, node)
                dst = self._drain_dst(len(res), node)
                if dst is None:
                    self.free_sequence(seq)
                    cold.append(seq)
                    continue
                for pi, phys in res:
                    self.table.migrate_page(phys, dst)
                    migrated += 1
                    if on_migrate is not None:
                        on_migrate(seq, pi, node, dst)
                self._placement[seq] = dst
                moved[seq] = dst
            except Exception:
                # destination lost mid-migration (its failover already
                # requeued whatever reached it) — cold path for this
                # victim, survivors re-pick a destination
                self.free_sequence(seq)
                cold.append(seq)
        self.table.release_shard_cache(node)
        return {"victims": victims, "migrated_pages": migrated,
                "cold": cold, "moved": moved}

    # -- per-node telemetry ---------------------------------------------------

    def node_tier_stats(self) -> List[Dict[str, int]]:
        """One stats dict per node — the aggregate ``tier_stats`` is the
        field-wise sum of these (each node owns its window and tier)."""
        return [dict(vars(ss)) for ss in self.table.shard_stats]

    # -- device programs (shard-local bodies) ---------------------------------

    def decode_step(self, params, state, page_table, lengths, tokens):
        return self._sharded_decode(params, state, page_table, lengths,
                                    tokens)

    def prefill_chunk_step(self, params, state, page_row, tokens, start,
                           n_valid):
        return self._sharded_chunk(params, state, page_row, tokens,
                                   start, n_valid)

    def _pool_hooks(self, n_local: int, page_table):
        """The two scaffold hooks every pool body shares: rebase global
        physical ids into this node's window (the append sentinel drops
        non-owned writes) and run ownership-masked attention partials
        merged across the pool axis.  ``page_table`` may be a [B, pps]
        batch table (decode/horizon) or a broadcast [C, pps] chunk
        table."""
        base = lax.axis_index(POOL_AXIS) * n_local
        local_table = page_table - base
        col_owned = (local_table >= 0) & (local_table < n_local)

        def append_target(phys, valid):
            local_new = phys - base
            owned = valid & (local_new >= 0) & (local_new < n_local)
            return jnp.where(owned, local_new, n_local)

        def attention(q, st, new_lengths):
            # quantized stores dequantize in the partial itself (the
            # same multiply on every node), so the LSE merge stays
            # device-invariant across pool shards
            o, m, l = self._attention_partial(q, st, local_table,
                                              col_owned, new_lengths)
            return combine_partials(o, m, l, POOL_AXIS).astype(self.dtype)

        return append_target, attention

    def _decode_body(self, params, state, page_table, lengths, tokens):
        """Per-node slice of one pool decode step — the shared horizon
        scaffold at H=1 (same unification as ``PagedServer.decode_step``)
        with the pool hooks plugged in: physical page ids are global,
        each node maps them into its own window (append and attention
        masked to owned pages) and the attention partials are merged
        across the pool axis."""
        append_target, attention = self._pool_hooks(state["k"].shape[1],
                                                    page_table)
        _, logits, state = self._fused_horizon_scan(
            params, state, page_table, lengths, tokens,
            (lengths > 0).astype(jnp.int32), jnp.int32(-1), horizon=1,
            append_target=append_target, attention=attention)
        return logits, state

    # -- fused decode horizon (sharded) ---------------------------------------

    def decode_horizon_step(self, params, state, page_table, lengths,
                            tokens, budget, eos_id, key=None,
                            temperature=None, top_p=None, streams=None,
                            *, horizon: int):
        if key is None:
            # shard_map specs are positional: materialize the sampling
            # quad (greedy ignores the values inside the traced
            # switch, so this costs nothing and keeps one spec set)
            key = jax.random.PRNGKey(0)
            temperature = jnp.float32(0.0)
            top_p = jnp.float32(1.0)
        if streams is None:
            streams = jnp.zeros(lengths.shape, jnp.int32)
        fn = self._sharded_horizons.get(horizon)
        if fn is None:
            in_specs, out_specs = shd.pool_horizon_specs(self.quantized)
            fn = jax.shard_map(
                lambda *a: self._horizon_body(*a, horizon=horizon),
                mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)
            self._sharded_horizons[horizon] = fn
        return fn(params, state, page_table, lengths, tokens, budget,
                  eos_id, key, temperature, top_p, streams)

    def _horizon_body(self, params, state, page_table, lengths,
                      tokens, budget, eos_id, key, temperature, top_p,
                      streams, *, horizon: int):
        """Per-node slice of one fused decode horizon.

        The shared ``_fused_horizon_scan`` scaffold with the pool's two
        hooks plugged in: the append target rebases physical ids into
        this node's window (non-owned appends drop via the sentinel),
        and attention runs ownership-masked partials merged across the
        pool axis per layer.  The merged logits' argmax — identical on
        every node — drives the next step, so control (lengths,
        budgets, EOS) stays replicated arithmetic: H tokens cost zero
        host interactions and exactly 3 collectives per layer per
        token, same as the per-token path.

        Ownership of every logical page in the horizon's reservation is
        fixed for the whole horizon (the table covers the pre-reserved
        extent; only the append *target* advances).
        """
        append_target, attention = self._pool_hooks(state["k"].shape[1],
                                                    page_table)
        return self._fused_horizon_scan(
            params, state, page_table, lengths, tokens,
            budget, eos_id, key, temperature, top_p, streams,
            horizon=horizon,
            append_target=append_target, attention=attention)

    # -- speculative draft-verify (sharded) -----------------------------------

    def decode_spec_step(self, params, state, page_table, lengths,
                         tokens, budget, eos_id, hist, hist_len, key,
                         temperature, top_p, streams=None, *,
                         horizon: int):
        if streams is None:
            streams = jnp.zeros(lengths.shape, jnp.int32)
        fn = self._sharded_specs.get(horizon)
        if fn is None:
            in_specs, out_specs = shd.pool_spec_specs(self.quantized)
            fn = jax.shard_map(
                lambda *a: self._spec_body(*a, horizon=horizon),
                mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)
            self._sharded_specs[horizon] = fn
        return fn(params, state, page_table, lengths, tokens, budget,
                  eos_id, hist, hist_len, key, temperature, top_p,
                  streams)

    def _spec_body(self, params, state, page_table, lengths, tokens,
                   budget, eos_id, hist, hist_len, key, temperature,
                   top_p, streams, *, horizon: int):
        """Per-node slice of one speculative draft-verify pass.

        The shared ``_spec_verify_scan`` scaffold with the pool hooks:
        the drafter reads the replicated history table (every node
        computes the identical candidates — no cross-node traffic for
        drafting), each node appends/attends only its owned pages with
        the per-position causal lengths, the LSE partials merge across
        the pool axis, and acceptance + sampling run on the *merged*
        logits with the replicated key — so the packed emission block
        is bit-identical on every node (the determinism
        tests/test_speculative.py pins against a 1-node PagedServer).
        """
        append_target, attention = self._pool_hooks(
            state["k"].shape[1], jnp.repeat(page_table, horizon, axis=0))
        return self._spec_verify_scan(
            params, state, page_table, lengths, tokens, budget, eos_id,
            hist, hist_len, key, temperature, top_p, streams,
            horizon=horizon,
            append_target=append_target, attention=attention)

    def _chunk_body(self, params, state, page_row, tokens, start,
                    n_valid):
        """Per-node slice of one prefill chunk: the shared chunk
        scaffold with the pool hooks — every node runs the layer stack
        on the chunk (replicated; each DockerSSD stores the full model),
        writes only the chunk K/V pages it owns via the masked scatter,
        attends over its own pages and merges the LSE partials, so the
        chunk's queries see the whole cached prefix wherever its pages
        live in the pool."""
        append_target, attention = self._pool_hooks(
            state["k"].shape[1], jnp.broadcast_to(
                page_row[None, :], (tokens.shape[1], page_row.shape[0])))

        return self._prefill_chunk_scan(
            params, state, page_row, tokens, start, n_valid,
            append_target=append_target,
            attention=lambda q, st, table, lengths:
                attention(q, st, lengths))

    def step_reference(self, tokens):
        raise NotImplementedError(
            "the pool path is validated against a 1-node PagedServer "
            "running the same workload (tests/test_pool.py, "
            "benchmarks/run.py pool)")
