"""Serving runtime.

Two paths:

  * ``make_serving_fns`` — production path: jitted prefill/decode with
    the D-Cache sharding rules (KV sequence-sharded over the ``model``
    axis = the storage pool; see runtime/sharding.py).  Used by
    ``launch/serve.py`` and the dry-run.
  * ``PagedServer`` — the paper's tiered mechanism made concrete on one
    device: a host-side **PageTableManager** (policy: LRU tiering,
    pinning, prefetch, admission accounting) over a device-resident
    **PageStore** with *stacked* per-layer pages, consumed by the Pallas
    ``paged_attention`` kernel.  One jitted ``decode_step`` advances
    every layer and every active sequence per token through one
    ``lax.scan`` over layers that carries the store whole: each layer
    appends the new K/V of every sequence in place at its layer index
    and the kernel reads that layer of the pages the table names, so no
    step program slices or restacks a layer of the store.  Prefill is
    **chunked**: each jitted
    ``prefill_chunk_step`` writes one pow2-bucketed chunk of prompt
    pages and attends over the paged context, and prompts whose prefix
    is already resident skip the covered pages entirely (the
    content-addressed **prefix page cache** in
    ``core.kv_tier.PageTableManager``: refcount shares + copy-on-write;
    DESIGN.md §Prefix page cache).  Host-side page management
    (eviction, page-in, CoW splits, table assembly) runs *between*
    jitted steps — the ISP-container split of the case study: policy
    at the host, data-path on the device.

The **fused decode horizon** (``decode(horizon=H)``) extends the same
split H tokens at a time: one jitted ``lax.scan`` over H decode steps
where the on-device argmax feeds the next step, page slots advance
against a horizon's worth of pre-reserved pages
(``PageTableManager.reserve_horizon``), per-sequence EOS/budget masks
stop finished sequences mid-horizon, and exactly one [H, B] token
transfer crosses the boundary per horizon — greedy outputs are
token-for-token identical to the per-token path (DESIGN.md §Decode
horizon).

**Speculative decoding** (``decode(speculative=True)``) turns the same
scaffold into a draft-verify loop: an n-gram / prompt-lookup drafter
(``draft_ngram`` — suffix-match over the sequence's own
prompt+generated history, a device-side table so drafting adds no host
round-trip) proposes up to H-1 candidate tokens per sequence, ONE
chunk-shaped pass verifies every candidate (per-step query positions
against the pre-reserved pages), the on-device acceptance mask keeps
the longest matched prefix plus the bonus token from the first
mismatch, and ``commit_horizon`` rolls the rest of the reservation
back.  Token selection is on-device throughout — greedy argmax or
temperature/top-p Gumbel sampling on a per-step PRNG key
(``SamplingConfig``), with rejection-sampling acceptance so
speculative sampling stays distribution-correct (DESIGN.md
§Speculative decoding).

**Hybrid models** (attention + Mamba-2 layers, granite-4.0-h) run the
same programs: the attention layers on pages, the Mamba layers on one
state slot per sequence carried beside them — prefill runs the chunked
SSD from the slot, decode the ``ssm_state_update`` kernel (DESIGN.md
§Hybrid models).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.kv_tier import (PAGE_DTYPES, PageStore, PageTableManager,
                                quantize_page_kv)
from repro.kernels import ops, ref as kref
from repro.kernels.paged_attention import (
    paged_attention as _paged_inner,
    paged_attention_q8 as _paged_q8_inner,
    pages_per_block)
from repro.kernels.ssm_state import ssm_state_update
from repro.models import layers as L
from repro.models.hybrid import layer_runs
from repro.models.mamba2 import (D_CONV, apply_mamba2_seq, mamba2_dims,
                                 mamba2_out, mamba2_step_in, ssd_step)
from repro.runtime import sharding as shd, tracing

NEG_INF = -1e30

#: what a model with recurrent layers cannot do here yet: its state
#: lives in one slot per sequence, with no copy at a prefix boundary and
#: no way to move it
NO_STATE_SNAPSHOTS = ("state snapshots at prefix boundaries and state "
                      "migration are not implemented")


def paged_attention_partial(q, k_pages, v_pages, local_table, col_owned,
                            lengths, k_scale=None, v_scale=None, *,
                            layer=None):
    """Paged decode attention returning online-softmax partials.

    The device contract of distributed paged attention (the pool hot
    path): score only the pages this node owns, fold them with a
    softmax, and hand back ``(o, m, l)`` — the output normalized over
    the owned pages plus its softmax statistics — so the caller can
    merge nodes exactly (``combine_partials``); on one node owning
    every page, ``o`` *is* the full attention.  This is the jnp form
    (CPU interpret mode); on TPU the Pallas ``paged_attention`` kernel
    computes the same contract (``return_stats``).

    q: [B, H, D]; k_pages/v_pages: *local* [P_node, page, Hkv, D], or
    the stacked [L, P_node, page, Hkv, D] read at ``layer`` (gathering
    only the pages the table names, never the layer's whole slice);
    local_table: [B, pps] local physical ids (garbage where not owned);
    col_owned: [B, pps] bool — does this node own that logical page;
    lengths: [B] post-append sequence lengths.
    ``k_scale``/``v_scale`` ([P_node, page, Hkv] f32, stacked like the
    pages; quantized stores only) dequantize in-register with the exact
    same multiply on every node, so the LSE merge stays device-invariant
    across pool shards.
    Returns (o [B, H, D] f32, m [B, H] f32, l [B, H] f32).
    """
    b, h, d = q.shape
    page, hkv = k_pages.shape[-3:-1]
    pps = local_table.shape[1]
    g = h // hkv
    sm_scale = 1.0 / math.sqrt(d)

    safe = jnp.where(col_owned, local_table, 0)
    at = safe if layer is None else (layer, safe)
    k = k_pages[at].astype(jnp.float32)          # [B, pps, page, Hkv, D]
    v = v_pages[at].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[at][..., None]           # fused dequant, no fp32
        v = v * v_scale[at][..., None]           # page materialization
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bptkd->bkgpt", qg, k) * sm_scale
    pos = (jnp.arange(pps, dtype=jnp.int32)[:, None] * page +
           jnp.arange(page, dtype=jnp.int32)[None, :])     # [pps, page]
    mask = (pos[None] < lengths[:, None, None]) & col_owned[:, :, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    sf = s.reshape(b, hkv, g, pps * page)
    mf = mask.reshape(b, 1, 1, pps * page)
    m = jnp.max(sf, axis=-1)                               # [b, hkv, g]
    # all-masked rows have m == NEG_INF; exp(NEG_INF - NEG_INF) == 1, so
    # the mask (not the score) must zero those probabilities
    p = jnp.where(mf, jnp.exp(sf - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgt,btkd->bkgd", p,
                     v.reshape(b, pps * page, hkv, d))
    # same acc / max(l, 1e-30) convention as the Pallas kernel
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def combine_partials(o, m, l, axis_name: str):
    """Cross-node merge of ``(o, m, l)`` partials: weight every node's
    normalized output by its share ``l * exp(m - m_glob)`` of the global
    softmax mass, and sum.  Nodes owning nothing contribute (0, NEG_INF,
    0) and vanish; a fully-masked (padding) slot has no mass anywhere
    and yields 0.  When one node owns all of a sequence's pages (the
    placed policy) its weight is exactly ``l / l == 1`` and every other
    weight exactly 0, so the merge returns that node's output bit for
    bit — pool serving then matches the single-node server exactly."""
    m_glob = lax.pmax(m, axis_name)
    w = l * jnp.exp(m - m_glob)
    w = w / jnp.maximum(lax.psum(w, axis_name), 1e-30)
    return lax.psum(o.astype(jnp.float32) * w[..., None], axis_name)


def make_serving_fns(model, mesh=None):
    """Returns (prefill_fn, decode_fn), jitted; sharded when mesh given."""
    if mesh is None:
        return (jax.jit(model.prefill), jax.jit(model.decode_step,
                                                donate_argnums=(1,)))
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shd.param_specs(mesh, params_shape))

    prefill = jax.jit(model.prefill, in_shardings=(pshard, None))

    def decode(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    decode_j = jax.jit(decode, donate_argnums=(1,),
                       in_shardings=(pshard, None, None))
    return prefill, decode_j


def _pow2(n: int) -> int:
    """Smallest power of two >= n (shape bucketing to bound retraces)."""
    return 1 << max(0, n - 1).bit_length()


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (horizon bucketing: a tail horizon
    runs as pow2 chunks — e.g. 5 -> 4 then 1 — so the compiled-program
    set stays O(log) *without* masked surplus steps burning full model
    forwards)."""
    return 1 << (max(n, 1).bit_length() - 1)


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """On-device token selection, threaded through ``decode`` /
    ``horizon_batch`` / ``spec_horizon_batch``.

    ``temperature <= 0`` is greedy argmax — the default, bit-identical
    to the historical ``greedy=True`` path.  ``temperature > 0``
    samples on device via Gumbel-max over the temperature-scaled,
    top-p-filtered distribution; the PRNG key derives from ``seed``
    (folded with the pass index host-side, the step index on device),
    so every pool node draws the identical sample from the merged
    logits and tokens stay device-invariant across shards."""
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingConfig()


def sampling_log_probs(logits, temperature, top_p):
    """Log-probs of the temperature/top-p target distribution.

    ``logits`` [..., V]; ``temperature``/``top_p`` [] f32 arrays
    (traced, so toggling sampling never retraces).  Tokens outside the
    nucleus — the smallest probability-sorted set with mass >=
    ``top_p`` (cutoff ties all kept) — go to NEG_INF and the rest
    renormalize.  This IS the distribution speculative acceptance must
    be correct against, so the verify pass scores drafted tokens with
    exactly these probabilities."""
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32) / t, axis=-1)
    p = jnp.exp(lp)
    srt = jnp.sort(p, axis=-1)[..., ::-1]
    mass_before = jnp.cumsum(srt, axis=-1) - srt
    cut = jnp.min(jnp.where(mass_before < top_p, srt, jnp.inf),
                  axis=-1, keepdims=True)
    lp = jnp.where(p >= cut, lp, NEG_INF)
    return lp - jax.nn.logsumexp(lp, axis=-1, keepdims=True)


def sampled_token(logits, sampling, stream: int, position: int) -> int:
    """Host-side mirror of the device sampler for ONE token: the token
    at absolute ``position`` of sequence ``stream``, drawn from
    ``logits`` [V] under ``sampling`` with the same
    per-(sequence, position) Gumbel-max key the fused scaffold uses.
    Greedy configs reduce to plain argmax.

    This is the admission-time selection a scheduler needs: the token
    after a (re-)prefill is chosen from host-visible logits, and it
    must equal the draw the device would have made at that position —
    otherwise a failover-requeued sequence resuming at temperature > 0
    would diverge from the uninterrupted run."""
    row = jnp.asarray(logits).reshape(-1)
    if sampling is None or sampling.greedy:
        return int(jnp.argmax(row))
    lp = sampling_log_probs(row, jnp.float32(sampling.temperature),
                            jnp.float32(sampling.top_p))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(sampling.seed),
                           int(stream) & 0x7FFFFFFF), int(position))
    g = jax.random.gumbel(key, lp.shape, jnp.float32)
    return int(jnp.argmax(lp + g))


# n-gram drafter tuning: a candidate site must match at least
# SPEC_MIN_MATCH trailing history tokens (a bigram minimum drowns in
# spurious matches on non-repetitive text — every false draft burns a
# verify position), and match quality is scored up to SPEC_MAX_MATCH
# trailing tokens (longer suffix agreement disambiguates cycles whose
# bigrams recur with different successors)
SPEC_MIN_MATCH = 3
SPEC_MAX_MATCH = 8


def draft_ngram(hist, hist_len, n_draft: int):
    """Device-side n-gram / prompt-lookup drafter.

    Suffix-match over the sequence's own prompt+generated token
    history: find the earlier site whose trailing tokens agree with the
    history's suffix on the longest run (scored up to
    ``SPEC_MAX_MATCH``, required >= ``SPEC_MIN_MATCH``; ties prefer a
    site with a full ``n_draft`` of successor tokens, then the latest
    one) and propose the tokens that followed it.  The history rides in
    as a replicated device table, so drafting costs zero host
    round-trips and every pool shard derives the identical candidates.

    hist: [B, T] int32 (prompt + generated incl. the pending token,
    garbage past ``hist_len``); hist_len: [B] int32.  Returns
    [B, n_draft] int32 candidates, -1 where nothing matched (a -1
    candidate can never equal a real token, so the verify pass rejects
    it for free)."""
    b, t = hist.shape
    ar = jnp.arange(t, dtype=jnp.int32)
    k = int(min(SPEC_MAX_MATCH, t))
    # suffix tokens newest-first: last_js[:, j] = hist[hl - 1 - j]
    idx = jnp.clip(hist_len[:, None] - 1 - jnp.arange(k)[None, :],
                   0, t - 1)
    last_js = jnp.take_along_axis(hist, idx, axis=1)         # [B, K]
    run = jnp.ones((b, t), bool)
    mlen = jnp.zeros((b, t), jnp.int32)
    for j in range(k):
        # hj[:, i] = hist[:, i - j] (the token j back from site i)
        hj = (jnp.pad(hist, ((0, 0), (j, 0)),
                      constant_values=-1)[:, :t] if j else hist)
        e = ((hj == last_js[:, j:j + 1]) & (ar[None, :] >= j) &
             ((hist_len[:, None] - 1 - j) >= 0))
        run = run & e
        mlen = mlen + run.astype(jnp.int32)
    valid = ((mlen >= SPEC_MIN_MATCH) & (ar[None, :] >= 1) &
             (ar[None, :] < (hist_len - 1)[:, None]))
    # successor tokens actually available after site i — the draft
    # length this site can fill.  Ranked FIRST: on a repeating stream
    # the deepest matches crowd the history tail where there is nothing
    # left to copy, so runway (how much we can draft) outranks match
    # depth (how sure we are), with depth and recency as tiebreaks
    runway = jnp.clip((hist_len[:, None] - 1) - ar[None, :], 0, n_draft)
    score = jnp.where(
        valid,
        (runway * (SPEC_MAX_MATCH + 1) + mlen) * t + ar[None, :], -1)
    best = jnp.max(score, axis=1)                            # [B]
    match = jnp.where(best >= 0, best % t, -1)
    di = match[:, None] + 1 + jnp.arange(n_draft, dtype=jnp.int32)[None]
    ok = (match >= 1)[:, None] & (di < hist_len[:, None])
    cand = jnp.take_along_axis(hist, jnp.clip(di, 0, t - 1), axis=1)
    return jnp.where(ok, cand, -1).astype(jnp.int32)


class PagedServer:
    """Tiered-KV serving for a TransformerLM on one device.

    All layers share one page table: a physical page id addresses the
    stacked KV ``[n_layers, page, Hkv, D]`` of that extent, so host<->HBM
    tiering moves whole stacked pages and the jitted step needs exactly
    one table per batch.  Batch size and table width are bucketed to
    powers of two, so the decode step compiles O(log) times, not per
    shape.

    A hybrid model (``cfg.layer_types``: attention and Mamba-2 layers,
    ``models/hybrid.py``) keeps pages for its attention layers only and
    its Mamba layers' recurrent state in ``state_slots`` per-sequence
    slots beside them (``PageStore``): a slot is claimed and zeroed at
    admission and released with the sequence, and the step programs
    carry the slots with the pages (:meth:`_hybrid_stack`).  Nothing
    copies a slot, so what would need a copy of the state refuses such
    a model: the prefix cache, speculation, preemption to the flash
    tier and the pool (``NO_STATE_SNAPSHOTS``).
    """

    def __init__(self, model, params, *, page_size: int = 16,
                 hbm_pages: Optional[int] = None, dtype=jnp.float32,
                 hbm_pages_per_layer: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 page_dtype: str = "fp32",
                 hbm_bytes: Optional[int] = None, state_slots: int = 8):
        if page_dtype not in PAGE_DTYPES:
            raise ValueError(f"page_dtype must be one of {PAGE_DTYPES}, "
                             f"got {page_dtype!r}")
        self.model = model
        self.cfg = model.cfg
        # recurrent (Mamba-2) layers keep one state slot per live
        # sequence beside the KV pages of the attention layers
        self.has_state = self.cfg.state_layers > 0
        self.state_slots = state_slots if self.has_state else 0
        if prefix_cache is None:
            prefix_cache = not self.has_state
        elif prefix_cache and self.has_state:
            raise ValueError(
                "the prefix page cache cannot serve a model with recurrent "
                "layers: a hit would skip the state of the shared prefix; "
                + NO_STATE_SNAPSHOTS)
        self.params = params
        self.dtype = dtype
        self.page = page_size
        self.page_dtype = page_dtype
        self.quantized = page_dtype in ("int8", "fp8")
        if hbm_bytes is not None:
            # capacity is a byte budget, not a page count: the window
            # holds however many (dtype-aware) stacked pages fit — the
            # quantized format's 2-4x page-count payoff at equal HBM
            pb = PageStore.stacked_page_bytes(
                n_layers=self.cfg.attn_layers, page_size=page_size,
                n_kv_heads=self.cfg.n_kv_heads, head_dim=self.cfg.hd,
                dtype=dtype, page_dtype=page_dtype)
            hbm_pages = max(1, int(hbm_bytes) // pb)
        elif hbm_pages is None:
            hbm_pages = (hbm_pages_per_layer
                         if hbm_pages_per_layer is not None else 64)
        self.hbm_pages = hbm_pages
        # prefix_cache=False ablates the shared-prefix page cache (every
        # admission computes every prompt token — the cold baseline the
        # benchmark's warm-speedup floor is measured against)
        self.prefix_cache = prefix_cache
        self.store = self._new_store()
        self.table = self._new_table()
        self._seqs: List[int] = []
        self._pending: Dict[int, int] = {}
        # prompt tokens of admissions whose chunked prefill is still
        # in flight (progress = the table's committed length);
        # _prefill_unmatched marks the ones whose lazy prefix match has
        # not run yet
        self._prefill_state: Dict[int, np.ndarray] = {}
        self._prefill_unmatched: set = set()
        self.prefill_tokens_computed = 0
        self._interpret = ops.interpret_mode()
        # interpret mode runs the partial-softmax attention in jnp: the
        # Pallas emulation's per-call cost would otherwise dominate the
        # very overhead the fused paths amortize.  On TPU every paged
        # attention is the kernel.
        self._jnp_attention = self._interpret
        # donating the page state lets XLA update the store in place;
        # CPU jit ignores donation (with a warning), so only opt in on
        # accelerators.
        donate = (1,) if not self._interpret else ()
        self._decode_jit = jax.jit(self.decode_step, donate_argnums=donate)
        self._chunk_jit = jax.jit(self.prefill_chunk_step,
                                  donate_argnums=donate)
        self._horizon_jit = jax.jit(self.decode_horizon_step,
                                    static_argnames=("horizon",),
                                    donate_argnums=donate)
        self._spec_jit = jax.jit(self.decode_spec_step,
                                 static_argnames=("horizon",),
                                 donate_argnums=donate)
        # prompt + generated (incl. pending) tokens per live sequence —
        # the drafter's lookup corpus; uploaded per spec pass like the
        # page table, never read back
        self._history: Dict[int, List[int]] = {}
        self.spec_lookup_window = 256
        # adaptive gate: speculation pays only while drafts land, so a
        # rolling acceptance-rate EMA below the floor routes passes to
        # the plain horizon, with periodic probe passes to reopen
        # the break-even acceptance rate rises with the draft depth (a
        # mostly-rejected H=16 verify costs the same device time as a
        # fallback pass that commits all 16), so the gate closes early
        self.spec_alpha_floor = 0.7
        self.spec_probe_every = 16
        self.spec_stats: Dict[str, object] = {}
        self.reset_speculation_stats()

    def _new_store(self) -> PageStore:
        """The store the config prescribes (used at init and when a failed
        donated step voids the window)."""
        cfg = self.cfg
        state = {}
        if self.has_state:
            d_inner, n_heads, conv_dim = mamba2_dims(cfg)
            state = dict(state_layers=cfg.state_layers,
                         state_slots=self.state_slots,
                         ssm_shape=(n_heads, cfg.ssm_head_dim,
                                    cfg.ssm_state),
                         # the tail flat: a [.., 3, C] store would be laid
                         # out with the 3 minor, padded to 128 lanes
                         conv_shape=((D_CONV - 1) * conv_dim,))
        return PageStore(n_layers=cfg.attn_layers, page_size=self.page,
                         hbm_pages=self.hbm_pages,
                         n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                         dtype=self.dtype, page_dtype=self.page_dtype,
                         **state)

    def _new_table(self) -> PageTableManager:
        """Table-manager factory (PoolServer overrides with a sharded
        manager bound to its placement policy)."""
        return PageTableManager(self.store)

    # -- public capacity API (admission control lives on these) --------------

    def pages_needed(self, n_tokens: int) -> int:
        return self.table.pages_needed(n_tokens)

    def has_state_slot(self) -> bool:
        """Can one more sequence be admitted as far as state slots go
        (always, for a model without recurrent layers)?"""
        return not self.has_state or self.table.free_slots > 0

    def sequence_ids(self) -> List[int]:
        return list(self._seqs)

    def pending_tokens(self) -> Dict[int, int]:
        """Next-token (greedy) continuation for each live sequence — the
        argmax produced by its last prefill/decode step."""
        return dict(self._pending)

    def set_pending(self, seq_id: int, token: int):
        """Override the pending next token for ``seq_id`` — the token
        the next decode call will feed first.  Schedulers doing sampled
        selection host-side (``sampled_token``) use this so the device
        continues from the token they actually reported; the drafter
        history entry mirroring the old pending token is rewritten to
        match (the fed token is what the drafter will see)."""
        tok = int(token)
        hist = self._history.get(seq_id)
        if hist and hist[-1] == self._pending.get(seq_id):
            hist[-1] = tok
        self._pending[seq_id] = tok

    def free_sequence(self, seq_id: int) -> int:
        """Retire a sequence: all its HBM + host-tier pages are released
        and immediately reusable.  Returns the number of pages freed."""
        freed = self.table.free_sequence(seq_id)
        if seq_id in self._seqs:
            self._seqs.remove(seq_id)
        self._pending.pop(seq_id, None)
        self._prefill_state.pop(seq_id, None)
        self._prefill_unmatched.discard(seq_id)
        self._history.pop(seq_id, None)
        return freed

    def _recover_store(self):
        """Failure cleanup for donated jitted calls.  On accelerators the
        step's inputs are donated, so a call that fails *during execution*
        has already consumed the store arrays; the resident page data is
        unrecoverable.  Drop every sequence and reopen an empty window so
        the server stays usable (callers resubmit) instead of poisoning
        all later steps with deleted buffers."""
        if not self.store.is_deleted():
            return
        stats, shard_stats = self.table.stats, self.table.shard_stats
        self.store = self._new_store()
        self.table = self._new_table()
        self.table.stats = stats           # telemetry continuity
        self.table.shard_stats = shard_stats
        self._seqs.clear()
        self._pending.clear()
        self._prefill_state.clear()
        self._prefill_unmatched.clear()
        self._history.clear()

    # -- shared transformer-block halves (used by the jitted decode /
    #    prefill bodies and the eager reference; only the attention
    #    middle differs between them) ----------------------------------------

    def _attn_inputs(self, lp, h, positions):
        """Pre-norm -> q/k/v projections -> RoPE at ``positions``."""
        cfg = self.cfg
        a = L.apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], a, cfg)
        if cfg.rope:
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
        if cfg.attention_multiplier is not None:
            # the kernel scales scores by 1/sqrt(D); the model's scale
            # rides on q
            q = q * (cfg.attention_multiplier * math.sqrt(cfg.hd))
        return q, k, v

    def _residual(self, x):
        """A residual branch at the model's ``residual_multiplier``."""
        r = self.cfg.residual_multiplier
        return x if r == 1.0 else x * r

    def _attn_out_ffn(self, lp, h, o_flat):
        """Attention output-projection residual + FFN residual.
        o_flat: [B, S, H*D]."""
        h = h + self._residual(o_flat @ lp["attn"]["wo"].astype(h.dtype))
        return self._ffn(lp, h)

    def _ffn(self, lp, h):
        """The FFN residual of a layer."""
        cfg = self.cfg
        m = L.apply_norm(lp["mlp_norm"], h, cfg.norm, cfg.norm_eps)
        if cfg.is_moe:
            mo, _ = L.apply_moe(lp["mlp"], m, cfg, no_drop=True)
        else:
            mo = L.apply_mlp(lp["mlp"], m, cfg.act)
        return h + self._residual(mo)

    def _embed(self, params, tokens):
        h = L.embed_tokens(params["embed"], tokens, self.dtype)
        m = self.cfg.embedding_multiplier
        return h if m == 1.0 else h * m

    def _final_norm(self, params, h):
        cfg = self.cfg
        return L.apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)

    def _unembed(self, params, h):
        cfg = self.cfg
        logits = L.unembed(params["embed"], params.get("lm_head"), h,
                           cfg.tie_embeddings)
        s = cfg.logits_scaling
        return logits if s == 1.0 else logits / s

    # -- jitted device programs ----------------------------------------------

    def _append_state(self, st, li, tgt, offs, k_new, v_new):
        """Scatter one new KV position per row into layer ``li`` of the
        stacked page state, in place: ``[li, tgt, offs]`` of every leaf
        (``tgt`` rows at the out-of-bounds sentinel are dropped).
        Quantized stores quantize **on device at write time**: codes
        and their per-slot scales land in one step, so the page arrays
        never hold full-precision data.
        k_new/v_new: [N, Hkv, D]; tgt/offs: [N]; li: [] int32."""
        new = {"k": k_new, "v": v_new}
        if self.quantized:
            new["k"], new["ks"] = quantize_page_kv(
                k_new, self.store.qmax, self.store.code_dtype)
            new["v"], new["vs"] = quantize_page_kv(
                v_new, self.store.qmax, self.store.code_dtype)
        return {n: (a.at[li, tgt, offs].set(new[n].astype(a.dtype),
                                            mode="drop")
                    if n in new else a)
                for n, a in st.items()}

    def _kernel_attention(self, q, st, li, page_table, lengths,
                          return_stats: bool = False):
        """The Pallas paged-attention kernel over layer ``li`` of the
        stacked page state: the fp kernel for full-precision stores,
        the fused-dequant ``paged_attention_q8`` for quantized ones
        (codes and their scales stream HBM->VMEM, dequant happens
        in-register — HBM traffic is the quantized bytes)."""
        if self.quantized:
            return _paged_q8_inner(q, st["k"], st["v"], st["ks"], st["vs"],
                                   page_table, lengths, layer=li,
                                   interpret=self._interpret,
                                   return_stats=return_stats)
        return _paged_inner(q, st["k"], st["v"], page_table, lengths,
                            layer=li, interpret=self._interpret,
                            return_stats=return_stats)

    def _attention_partial(self, q, st, li, table, owned, lengths):
        """``(o, m, l)`` partials of layer ``li`` over the pages
        ``owned`` marks in ``table`` — the contract of
        :func:`paged_attention_partial`, computed by the Pallas kernel
        (not-owned entries become the kernel's negative skip ids) or, in
        interpret mode, in jnp."""
        if self._jnp_attention:
            return paged_attention_partial(
                q, st["k"], st["v"], table, owned, lengths,
                k_scale=st.get("ks"), v_scale=st.get("vs"), layer=li)
        return self._kernel_attention(q, st, li,
                                      jnp.where(owned, table, -1),
                                      lengths, return_stats=True)

    def _layer_stack(self, params, h, state, positions, tgt, offs,
                     attention, live=None, n_valid=None):
        """The layer loop every step program runs: one ``lax.scan`` with
        carry ``(h, state)`` over ``(params["layers"], layer index)``.
        The stacked page state is carried whole — never a scan input or
        output, so no layer's pages are sliced out, restacked or
        copied.  Each layer appends the new K/V at ``[li, tgt, offs]``
        in place and ``attention(q, state, li) -> [N, H, D]`` reads
        layer ``li`` of the pages its table names.

        h: [B, S, d] for the S new positions of each of B rows
        (``positions`` [B, S] or broadcastable); tgt/offs: [B*S] append
        targets.  A model with recurrent layers runs
        :meth:`_hybrid_stack` (``live``/``n_valid`` are its).  Returns
        (h, state)."""
        cfg = self.cfg
        if self.has_state:
            return self._hybrid_stack(params, h, state, positions, tgt,
                                      offs, attention, live, n_valid)
        b, s = h.shape[:2]
        n = b * s

        def body(carry, xs):
            hh, st = carry
            lp, li = xs
            q, k, v = self._attn_inputs(lp, hh, positions)
            st = self._append_state(st, li, tgt, offs,
                                    k.reshape(n, cfg.n_kv_heads, cfg.hd),
                                    v.reshape(n, cfg.n_kv_heads, cfg.hd))
            o = attention(q.reshape(n, cfg.n_heads, cfg.hd)
                          .astype(self.dtype), st, li)
            return (self._attn_out_ffn(lp, hh, o.reshape(b, s, -1)),
                    st), None

        (h, state), _ = lax.scan(
            body, (h, state),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        return h, state

    def _hybrid_stack(self, params, h, state, positions, tgt, offs,
                      attention, live, n_valid):
        """The layer loop of a model with attention and Mamba-2 layers
        (``cfg.layer_types``): one ``lax.scan`` over the periods of the
        layer pattern, whose body runs each run of one kind as a loop
        over that kind's stacked layers, indexed from the whole stack.
        The state — KV pages of the attention layers, state slots of
        the Mamba layers — is carried whole, as in :meth:`_layer_stack`:
        attention layer ``ai`` appends at ``[ai, tgt, offs]`` and reads
        through ``attention``; Mamba layer ``mi`` reads and writes its
        rows' slots at ``[mi, slot]`` in place.

        Row ``r``'s slot is ``state["rows"][r]``; a row that ``live``
        ([B] bool, decode) marks dead, or a prefill chunk with no valid
        token, updates no slot.  S > 1 is a prefill chunk of one
        sequence whose positions from ``n_valid`` on are padding."""
        cfg = self.cfg
        b, s = h.shape[:2]
        n = b * s
        n_periods, runs = layer_runs(cfg.layer_types)
        per = {k: sum(c for t, c in runs if t == k)
               for k in ("attention", "mamba")}
        slots = state["rows"][:b]
        if live is not None:
            slots = jnp.where(live, slots, -1)
        if n_valid is not None:
            slots = jnp.where(n_valid > 0, slots, -1)

        def layer_of(stack, i):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                params[stack])

        def attn_layer(ai, carry):
            hh, st = carry
            lp = layer_of("attn_layers", ai)
            q, k, v = self._attn_inputs(lp, hh, positions)
            st = self._append_state(st, ai, tgt, offs,
                                    k.reshape(n, cfg.n_kv_heads, cfg.hd),
                                    v.reshape(n, cfg.n_kv_heads, cfg.hd))
            o = attention(q.reshape(n, cfg.n_heads, cfg.hd)
                          .astype(self.dtype), st, ai)
            return self._attn_out_ffn(lp, hh, o.reshape(b, s, -1)), st

        def mamba_layer(mi, carry):
            hh, st = carry
            lp = layer_of("mamba_layers", mi)
            a = L.apply_norm(lp["norm"], hh, "rmsnorm", cfg.norm_eps)
            if s == 1:
                o, st = self._mamba_step(lp["mamba"], a[:, 0], st, mi, slots)
                o = o[:, None]
            else:
                o, st = self._mamba_chunk(lp["mamba"], a, st, mi, slots[0],
                                          n_valid)
            return self._ffn(lp, hh + self._residual(o)), st

        layer_fns = {"attention": attn_layer, "mamba": mamba_layer}

        def period(carry, p):
            seen = {"attention": 0, "mamba": 0}
            for kind, count in runs:
                fn, base = layer_fns[kind], p * per[kind] + seen[kind]
                if count == 1:
                    carry = fn(base, carry)
                else:
                    carry = lax.fori_loop(
                        0, count,
                        lambda j, c, fn=fn, base=base: fn(base + j, c),
                        carry)
                seen[kind] += count
            return carry, None

        (h, state), _ = lax.scan(period, (h, state),
                                 jnp.arange(n_periods, dtype=jnp.int32))
        return h, state

    def _mamba_step(self, p, a, st, mi, slots):
        """One token of Mamba layer ``mi`` for every row: the conv over
        the slot's carried tail, then the state update — the
        ``ssm_state_update`` kernel, or ``ssd_step`` in jnp where the
        attention runs in jnp too.  Rows with a negative slot read the
        store's spare slot and write nothing.  a: [B, d] normed input.
        Returns (out [B, d], state)."""
        cfg = self.cfg
        n_total = st["ssm"].shape[1]
        read = jnp.where(slots >= 0, slots, n_total - 1)
        write = jnp.where(slots >= 0, slots, n_total)   # dropped
        b = a.shape[0]
        z, xs, dt, bm, cm, tail = mamba2_step_in(
            p, a, cfg, st["conv"][mi, read].reshape(b, D_CONV - 1, -1))
        conv = st["conv"].at[mi, write].set(
            tail.reshape(b, -1).astype(jnp.float32), mode="drop")
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        D = p["d_skip"].astype(jnp.float32)
        if self._jnp_attention:
            y, new = ssd_step(xs, dt, dt * A[None], bm, cm,
                              st["ssm"][mi, read])
            y = y + xs * D[None, :, None]
            ssm = st["ssm"].at[mi, write].set(new, mode="drop")
        else:
            y, ssm = ssm_state_update(st["ssm"], xs, dt, A, bm, cm, D,
                                      slots, mi, interpret=self._interpret)
        y = y.reshape(b, -1).astype(a.dtype)
        return mamba2_out(p, y, z, cfg.norm_eps), {**st, "ssm": ssm,
                                                   "conv": conv}

    def _mamba_chunk(self, p, a, st, mi, slot, n_valid):
        """A prefill chunk of one sequence through Mamba layer ``mi``:
        the chunked SSD (``mamba_chunk_size``) from the slot's state and
        conv tail; the final state and the tail at the chunk's true
        length go back to the slot.  a: [1, C, d]."""
        cfg = self.cfg
        slot = jnp.where(slot >= 0, slot, st["ssm"].shape[1] - 1)

        def read(a):
            return lax.dynamic_slice(a, (mi, slot) + (0,) * (a.ndim - 2),
                                     (1, 1) + a.shape[2:])[0]

        def write(a, x):
            # an in-bounds update in place (``.at[].set`` would guard
            # the index with a select over the whole array)
            return lax.dynamic_update_slice(
                a, x.astype(a.dtype)[None], (mi, slot) + (0,) * (a.ndim - 2))

        out, tail, h_t = apply_mamba2_seq(
            p, a, cfg, read(st["conv"]).reshape(1, D_CONV - 1, -1),
            read(st["ssm"]),
            chunk=min(cfg.ssm_chunk, a.shape[1]), eps=cfg.norm_eps,
            n_valid=n_valid)
        return out, {**st, "ssm": write(st["ssm"], h_t),
                     "conv": write(st["conv"], tail.reshape(1, -1))}

    def decode_step(self, params, state, page_table, lengths, tokens):
        """One fused decode step for the whole active batch — the
        horizon scaffold run at H=1, so per-token/horizon token identity
        holds by construction rather than by test-enforced parallel
        bodies.  The attention is the Pallas ``paged_attention`` kernel
        (it stays the benchmark baseline); longer horizons swap in the
        LSE-partial form via their own hook.

        state: the :meth:`PageStore.device_state` pytree ({"k","v"}
        [L, P, page, Hkv, D] plus {"ks","vs"} [L, P, page, Hkv] when
        quantized); page_table: [B, pps] int32 physical ids; lengths:
        [B] int32 committed length per sequence (0 marks a padding
        slot); tokens: [B] int32.  Returns (logits [B, V] f32, state).
        """
        n_phys = state["k"].shape[1]
        _, logits, state = self._fused_horizon_scan(
            params, state, page_table, lengths, tokens,
            (lengths > 0).astype(jnp.int32), jnp.int32(-1), horizon=1,
            # out-of-bounds sentinel => scatter drops padding slots
            append_target=lambda phys, valid:
                jnp.where(valid, phys, n_phys),
            attention=lambda q, st, li, new_lengths:
                self._kernel_attention(q, st, li, page_table, new_lengths))
        return logits, state

    # -- fused decode horizon -------------------------------------------------

    def _horizon_attention(self, q, st, li, page_table, lengths):
        """Per-step decode attention inside the fused horizon loop.

        On TPU this is the Pallas ``paged_attention`` kernel at layer
        ``li``; in CPU interpret mode it is the jnp partial form (see
        ``_jnp_attention``), whose normalized output is exactly the full
        softmax when every page is owned.  Both close the same
        fused-dequant contract on quantized states.
        q: [B, H, D]; returns [B, H, D]."""
        if not self._jnp_attention:
            return self._kernel_attention(q, st, li, page_table, lengths)
        o, _, _ = self._attention_partial(
            q, st, li, page_table, jnp.ones(page_table.shape, bool),
            lengths)
        return o.astype(q.dtype)

    def _fused_horizon_scan(self, params, state, page_table, lengths,
                            tokens, budget, eos_id, key=None,
                            temperature=None, top_p=None, streams=None,
                            *, horizon: int,
                            append_target, attention):
        """The fused-step scaffold shared by the single-node and pool
        horizon bodies: one ``lax.scan`` over ``horizon`` decode steps
        where the on-device argmax feeds the next step, page slots
        advance against the reservation, and EOS/budget masks stop
        finished sequences.  The two hooks are the only places the
        paths differ:

        ``append_target(phys, valid) -> [B]`` maps each sequence's tail
        physical page to the scatter row (out-of-bounds sentinel drops
        finished/padding/non-owned appends); ``attention(q, st, li,
        new_lengths) -> [B, H, D]`` closes the paged-attention contract
        over layer ``li`` of the stacked state (locally normalized, or
        ownership-masked + pool-merged).

        Returns (emitted [H, B], last step's logits [B, V] f32, state)
        — the logits make H=1 *be* the per-token decode step (one
        scaffold, token identity by construction).

        ``key``/``temperature``/``top_p`` enable on-device sampling:
        each row's draw folds ``(streams[b], absolute position)`` into
        the key — ``streams`` is the [B] stable per-sequence id, the
        position is the emitted token's 1-based index in its sequence —
        so a sampled token is a pure function of (seed, sequence,
        position).  That is what makes sampling reproducible across
        failover re-prefill (same sequence, same positions => same
        draws, regardless of batch slot, pass boundaries or which node
        runs the step) and identical between the plain and speculative
        paths.  ``temperature <= 0`` falls through to the greedy argmax
        *inside* the traced switch, so toggling sampling never retraces
        and greedy outputs stay bit-identical to the key-free program.
        """

        def step(carry, i):
            state, lengths, tokens, budget = carry
            valid = (budget > 0) & (lengths > 0)
            pos = lengths[:, None]
            pidx = lengths // self.page
            offs = lengths % self.page
            phys = jnp.take_along_axis(page_table, pidx[:, None],
                                       axis=1)[:, 0]
            tgt = append_target(phys, valid)
            new_lengths = lengths + valid.astype(jnp.int32)

            h = self._embed(params, tokens[:, None])
            h, state = self._layer_stack(
                params, h, state, pos, tgt, offs,
                lambda q, st, li: attention(q, st, li, new_lengths),
                live=valid)
            logits = self._unembed(params,
                                   self._final_norm(params, h))[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if key is not None:
                # lax.cond (not where): greedy passes must not pay the
                # top-p sort + Gumbel draw at runtime
                def _sample(lg):
                    lp = sampling_log_probs(lg, temperature, top_p)

                    def draw(s, p, row_lp):
                        k = jax.random.fold_in(
                            jax.random.fold_in(key, s), p)
                        g = jax.random.gumbel(k, row_lp.shape,
                                              jnp.float32)
                        return jnp.argmax(row_lp + g).astype(jnp.int32)
                    # new_lengths is the emitted token's 1-based
                    # position — the coordinate the spec verify path
                    # folds too
                    return jax.vmap(draw)(streams, new_lengths, lp)
                nxt = lax.cond(temperature > 0, _sample,
                               lambda lg: jnp.argmax(
                                   lg, axis=-1).astype(jnp.int32),
                               logits)
            emitted = jnp.where(valid, nxt, -1)
            # the token just emitted consumed one budget slot; EOS zeroes
            # what's left so the next step goes inactive
            budget = jnp.where(valid & (nxt == eos_id), 0,
                               budget - valid.astype(jnp.int32))
            tokens = jnp.where(valid, nxt, tokens)
            return (state, new_lengths, tokens, budget), \
                (emitted, logits.astype(jnp.float32))

        (state, lengths, tokens, budget), (emitted, logits) = \
            lax.scan(step, (state, lengths, tokens, budget),
                     jnp.arange(horizon, dtype=jnp.int32))
        return emitted, logits[-1], state

    def decode_horizon_step(self, params, state, page_table, lengths,
                            tokens, budget, eos_id, key=None,
                            temperature=None, top_p=None, streams=None,
                            *, horizon: int):
        """``horizon`` fused decode steps in ONE device program.

        A single ``lax.scan`` over the horizon: each step appends the
        fed token's K/V against the pre-reserved page table (page-slot
        advance on device — ``lengths // page`` indexes into the
        horizon reservation), runs the layer stack, takes the greedy
        argmax **on device**, and feeds it to the next step.  Per-
        sequence EOS and token budgets are masked on device too, so a
        finished sequence stops appending mid-horizon without a host
        round-trip.  Exactly one token transfer happens per horizon:
        the stacked [horizon, B] emissions (-1 marks "no token").

        page_table: [B, pps] physical ids covering the *reservation*
        (``PageTableManager.reserve_horizon``); lengths: [B] committed
        lengths (0 marks padding slots); tokens: [B] the pending token
        per sequence; budget: [B] int32 tokens this sequence may still
        produce (device-side min of max_tokens and the caller's ask);
        eos_id: [] int32, -1 disables EOS stopping.

        Returns (emitted [horizon, B] int32, last step's logits [B, V],
        state).
        """
        n_phys = state["k"].shape[1]
        return self._fused_horizon_scan(
            params, state, page_table, lengths, tokens,
            budget, eos_id, key, temperature, top_p, streams,
            horizon=horizon,
            # out-of-bounds sentinel => scatter drops finished/padding
            append_target=lambda phys, valid:
                jnp.where(valid, phys, n_phys),
            attention=lambda q, st, li, new_lengths:
                self._horizon_attention(q, st, li, page_table,
                                        new_lengths))

    # -- speculative decoding (draft-verify on the horizon scaffold) ----------

    def _spec_verify_scan(self, params, state, page_table, lengths,
                          tokens, budget, eos_id, hist, hist_len, key,
                          temperature, top_p, streams=None, *,
                          horizon: int,
                          append_target, attention):
        """The draft-verify scaffold shared by the single-node and pool
        speculative bodies (the hooks mirror
        :meth:`_prefill_chunk_scan`'s — speculation verifies a
        *chunk-shaped* batch of candidate positions, not a sequential
        horizon).

        One pass: ``draft_ngram`` proposes ``horizon-1`` candidates per
        sequence from the device-resident history table; the fed block
        ``[pending, d_1 .. d_{H-1}]`` runs the layer stack as ``horizon``
        decode-shaped queries with per-position causal lengths (one
        pass of the layer loop, :meth:`_layer_stack` — the H-position
        forward costs one model pass, which is the entire speedup);
        position ``j``'s logits then judge candidate ``d_{j+1}``.
        Acceptance on device: greedy mode accepts while
        ``argmax == candidate``; sampling
        mode uses *Gumbel coupling* — pre-draw the target token from
        the same per-(stream, position) key the plain fused horizon
        folds, accept a candidate iff it equals that target, and emit
        the target either way.  For a point-mass draft this IS
        rejection sampling (a candidate ``d`` is accepted with
        probability exactly ``p(d)``, and the emitted marginal is the
        sampling target), with the stronger property that the sampled
        stream is token-identical to the non-speculative path — the
        invariant failover requeue and the chaos suite check.  The
        longest ok-prefix plus the bonus token from the first mismatch
        is emitted; everything downstream of the first break is masked
        to -1 so ``commit_horizon`` rolls its pages back.

        Returns (packed [horizon+1, B] int32 — emitted rows then the
        per-sequence drafted-count row, ONE device->host transfer —
        and the page state).
        """
        b = tokens.shape[0]
        pps = page_table.shape[1]
        hzn = horizon

        draft = draft_ngram(hist, hist_len, hzn - 1)          # [B, H-1]
        n_drafted = jnp.sum((draft >= 0).astype(jnp.int32), axis=1)
        fed = jnp.concatenate([tokens[:, None], jnp.maximum(draft, 0)],
                              axis=1)                          # [B, H]
        steps = jnp.arange(hzn, dtype=jnp.int32)[None, :]      # [1, H]
        pos = lengths[:, None] + steps                         # [B, H]
        # appends stay inside the reservation: a position past the
        # budget was never reserved a page, so it must not scatter
        append_ok = (steps < budget[:, None]) & (lengths[:, None] > 0)
        pidx = jnp.clip(pos // self.page, 0, pps - 1)
        offs = (pos % self.page).reshape(-1)
        phys = jnp.take_along_axis(page_table, pidx, axis=1)
        tgt = append_target(phys.reshape(-1), append_ok.reshape(-1))
        # per-position causal extent; 0 fully masks dead positions
        row_lengths = jnp.where(append_ok, pos + 1, 0).reshape(-1)

        h = self._embed(params, fed)
        h, state = self._layer_stack(
            params, h, state, pos, tgt, offs,
            lambda q, st, li: attention(q, st, li, row_lengths))
        logits = self._unembed(params, self._final_norm(params, h)
                               ).astype(jnp.float32)

        greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # candidate that position j's logits verify: d_{j+1}; the last
        # position has none (its emission is the bonus token)
        d_next = jnp.concatenate(
            [draft, jnp.full((b, 1), -1, jnp.int32)], axis=1)  # [B, H]

        has_draft = d_next >= 0

        def _greedy_sel(lg):
            return greedy_tok == d_next, greedy_tok

        def _sample_sel(lg):
            # Gumbel coupling: one pre-drawn target per (stream,
            # absolute position) — position j's emission lands at
            # 1-based position pos[:, j] + 1, the coordinate the plain
            # fused horizon folds — every pool node draws the same
            lp = sampling_log_probs(lg, temperature, top_p)

            def draw_row(s, row_pos, row_lp):
                def one(p, l):
                    k = jax.random.fold_in(jax.random.fold_in(key, s),
                                           p)
                    g = jax.random.gumbel(k, l.shape, jnp.float32)
                    return jnp.argmax(l + g).astype(jnp.int32)
                return jax.vmap(one)(row_pos + 1, row_lp)
            target = jax.vmap(draw_row)(streams, pos, lp)      # [B, H]
            return target == d_next, target

        # lax.cond (not where): a greedy pass must not pay the top-p
        # sort + H Gumbel draws at runtime
        accept_raw, out_tok = lax.cond(temperature > 0, _sample_sel,
                                       _greedy_sel, logits)
        accept = accept_raw & has_draft                        # [B, H]

        # longest ok-prefix: position j emits iff every earlier position
        # accepted its candidate, stayed under budget, and did not EOS
        live0 = (budget > 0) & (lengths > 0)
        cont = accept & (out_tok != eos_id) & (steps + 1 < budget[:, None])
        chain = jnp.cumprod(cont.astype(jnp.int32), axis=1)
        ok = live0[:, None] & jnp.concatenate(
            [jnp.ones((b, 1), bool), chain[:, :-1].astype(bool)], axis=1)
        emitted = jnp.where(ok, out_tok, -1).astype(jnp.int32)
        packed = jnp.concatenate([emitted.T, n_drafted[None, :]], axis=0)
        return packed, state

    def decode_spec_step(self, params, state, page_table, lengths,
                         tokens, budget, eos_id, hist, hist_len, key,
                         temperature, top_p, streams=None, *,
                         horizon: int):
        """One jitted speculative draft-verify pass on one device.

        Arguments as :meth:`decode_horizon_step` plus ``hist``
        [B, T] int32 / ``hist_len`` [B] (the drafter's history table),
        ``key`` (the pass PRNG key) and ``temperature``/``top_p`` []
        f32.  Returns (packed [horizon+1, B] int32, state) — see
        :meth:`_spec_verify_scan`.
        """
        n_phys = state["k"].shape[1]
        # every flattened query row attends over its sequence's table
        rows_table = jnp.repeat(page_table, horizon, axis=0)
        return self._spec_verify_scan(
            params, state, page_table, lengths, tokens, budget, eos_id,
            hist, hist_len, key, temperature, top_p, streams,
            horizon=horizon,
            append_target=lambda phys, valid:
                jnp.where(valid, phys, n_phys),
            attention=lambda q, st, li, row_lengths:
                self._horizon_attention(q, st, li, rows_table,
                                        row_lengths))

    def _prefill_chunk_scan(self, params, state, page_row, tokens, start,
                            n_valid, *, append_target, attention):
        """The prefill-chunk scaffold shared by the single-node and pool
        chunk bodies (the chunk-shaped sibling of
        :meth:`_fused_horizon_scan`, with the same two hooks): append
        the chunk's K/V into the sequence's pages, then attend every
        chunk position over the *paged* context — the cached/committed
        prefix plus the chunk itself, causally — as decode-shaped
        queries with per-position length ``pos+1``.

        ``append_target(phys, valid) -> [C]`` maps each position's
        destination page to the scatter row (sentinel drops padding /
        non-owned writes); ``attention(q, st, li, table, lengths) ->
        [C, H, D]`` closes the paged-attention contract over layer
        ``li`` of the stacked state.
        """
        c = tokens.shape[1]
        pps = page_row.shape[0]
        pos_i = jnp.arange(c, dtype=jnp.int32)
        wpos = start + pos_i                      # absolute positions
        positions = wpos[None, :]
        valid_w = pos_i < n_valid
        pidx = jnp.clip(wpos // self.page, 0, pps - 1)
        offs = wpos % self.page
        phys_w = append_target(page_row[pidx], valid_w)
        # per-position causal extent; 0 fully masks padding queries
        lengths_q = jnp.where(valid_w, wpos + 1, 0)
        table = jnp.broadcast_to(page_row[None, :], (c, pps))

        h = self._embed(params, tokens)
        h, state = self._layer_stack(
            params, h, state, positions, phys_w, offs,
            lambda q, st, li: attention(q, st, li, table, lengths_q),
            n_valid=n_valid)
        h = self._final_norm(params, h)
        last = lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
        logits = self._unembed(params, last)[0, 0]
        return logits.astype(jnp.float32), state

    def prefill_chunk_step(self, params, state, page_row, tokens, start,
                           n_valid):
        """One jitted prefill chunk on one device.

        page_row: [pps] int32 physical ids covering positions
        [0, start + n_valid); tokens: [1, C] int32 (C a pow2 bucket,
        garbage past n_valid); start: [] int32 committed tokens before
        this chunk; n_valid: [] int32 true chunk length.  Returns
        (last-valid-position logits [V] f32, state).
        """
        n_phys = state["k"].shape[1]
        return self._prefill_chunk_scan(
            params, state, page_row, tokens, start, n_valid,
            # out-of-bounds sentinel => the scatter drops chunk padding
            append_target=lambda phys, valid:
                jnp.where(valid, phys, n_phys),
            attention=self._horizon_attention)

    # -- request handling -----------------------------------------------------

    def begin_request(self, seq_id: int, prompt: np.ndarray) -> int:
        """Open an admission: queue the prompt for :meth:`prefill_chunk`
        calls.  The cached-prefix match itself runs lazily at the first
        chunk — a queued admission neither holds shared pages (they
        would be unevictable) nor misses pages an admission ahead of it
        in the queue is still about to register.  Returns the number of
        prompt tokens the cache covers *right now* (telemetry/routing;
        the lazy match can only cover more)."""
        prompt = np.asarray(prompt, np.int32)
        assert int(prompt.shape[0]) >= 1, "empty prompt"
        if self.has_state:
            with tracing.span("server.state.reset"):
                # the sequence starts from the zero state in a slot of
                # its own, released by free_sequence
                self.store.zero_slot(self.table.claim_slot(seq_id))
        self.table.add_sequence(seq_id)
        self._seqs.append(seq_id)
        self._prefill_state[seq_id] = prompt
        self._history[seq_id] = [int(t) for t in prompt]
        if not self.prefix_cache:
            return 0
        self._prefill_unmatched.add(seq_id)
        return self.table.probe_prefix(seq_id, prompt)

    def prefill_pending(self, seq_id: int) -> int:
        """Prompt tokens still to prefill (0 = admission complete)."""
        prompt = self._prefill_state.get(seq_id)
        if prompt is None:
            return 0
        return int(prompt.shape[0]) - self.table.length(seq_id)

    def prefill_chunk(self, seq_id: int, chunk: Optional[int] = None):
        """Run ONE jitted prefill chunk of at most ``chunk`` tokens
        (default: the whole remaining suffix).  The chunk length is
        bucketed UP to a power of two and the page row to a pow2 width,
        so admissions of any prompt length compile O(log) programs.
        Returns the last prompt position's logits [V] when this chunk
        completes the prompt, else None.

        Like the kernel view it feeds, the active working set must fit
        the HBM window (admission control's ``pages_needed`` contract);
        a prompt needing more pages than the window raises the same
        pinned-working-set error the per-token path raises.
        """
        prompt = self._prefill_state[seq_id]
        s = int(prompt.shape[0])
        with tracing.span("server.prefill") as counts:
            with tracing.span("server.prefill.plan"):
                try:
                    if seq_id in self._prefill_unmatched:
                        # lazy cached-prefix match (see begin_request):
                        # map shares, skip their prefill compute entirely
                        self._prefill_unmatched.discard(seq_id)
                        self.table.match_prefix(seq_id, prompt)
                    start = self.table.length(seq_id)
                    c = s - start if chunk is None else \
                        min(int(chunk), s - start)
                    try:
                        rows = self.table.ensure_resident(
                            seq_id, pin=True, n_tokens=start + c)
                        if start % self.page:
                            # the chunk's first write lands mid-page:
                            # CoW-split a shared prefix tail before the
                            # device touches it
                            self.table.make_writable(seq_id,
                                                     start // self.page)
                            rows = self.table.row(seq_id, len(rows))
                    finally:
                        self.table.unpin_all()
                except Exception:
                    # rejected admissions must not leak window pages or
                    # leave a zero-length ghost in the live set
                    self.free_sequence(seq_id)
                    raise
                row = np.zeros((_pow2(len(rows)),), np.int32)
                row[:len(rows)] = rows
                tokens = np.zeros((1, _pow2(c)), np.int32)
                tokens[0, :c] = prompt[start:start + c]
            counts.update(tokens=c, final=start + c == s)
            with tracing.span("server.prefill.dispatch"):
                try:
                    self._set_rows([seq_id])
                    logits, state = self._chunk_jit(
                        self.params, self.store.device_state(),
                        jnp.asarray(row), jnp.asarray(tokens),
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(c, jnp.int32))
                except Exception:
                    # a failure inside the donated jit call also voids
                    # the store
                    self.free_sequence(seq_id)
                    self._recover_store()
                    raise
                self.store.adopt(state)
            self.table.set_length(seq_id, start + c)
            self.prefill_tokens_computed += c
            if start + c < s:
                return None
            # admission complete: index the prompt's pages for later
            # sharers
            del self._prefill_state[seq_id]
            if self.prefix_cache:
                self.table.register_prefix(seq_id, prompt)
            with tracing.span("server.prefill.wait"):
                self._pending[seq_id] = int(jnp.argmax(logits))
            if seq_id in self._history:
                # the pending token is the first generated one: it will
                # be fed (and is thus drafter-visible) before it is
                # re-emitted
                self._history[seq_id].append(self._pending[seq_id])
        return logits

    def add_request(self, seq_id: int, prompt: np.ndarray, *,
                    chunk: Optional[int] = None):
        """Admit a sequence: cached-prefix match, then chunked jitted
        prefill of only the uncached suffix (``chunk=None`` runs the
        suffix as a single chunk — the blocking admission of the
        pre-chunking servers; schedulers that interleave admission with
        decode drive :meth:`begin_request`/:meth:`prefill_chunk`
        directly).  Returns the last prompt position's logits [V]."""
        self.begin_request(seq_id, prompt)
        logits = None
        while logits is None:
            logits = self.prefill_chunk(seq_id, chunk)
        return logits

    def prefix_hit_rate(self) -> float:
        """Fraction of all admitted prompt tokens served from the
        prefix cache instead of computed."""
        saved = self.table.stats.prefix_tokens
        total = saved + self.prefill_tokens_computed
        return saved / total if total else 0.0

    # -- one committed batched step -------------------------------------------

    def _set_rows(self, seqs: List[int]) -> None:
        """Name each row's state slot for the next step program."""
        if self.has_state:
            self.store.set_rows([self.table.slot(s) for s in seqs])

    def _plan_step(self, seqs: List[int]):
        """Host-side page management for one decode step: make every
        active page resident + pinned, then build the padded device
        inputs.  Shapes are bucketed to powers of two."""
        try:
            rows = [self.table.prepare_append(s) for s in seqs]
        except Exception:
            self.table.unpin_all()
            raise
        lengths = [self.table.length(s) for s in seqs]
        pps = _pow2(max(len(r) for r in rows))
        b2 = _pow2(len(seqs))
        table = np.zeros((b2, pps), np.int32)
        for i, r in enumerate(rows):
            table[i, :len(r)] = r
        lens = np.zeros((b2,), np.int32)
        lens[:len(seqs)] = lengths
        return jnp.asarray(table), jnp.asarray(lens)

    def step_batch(self, tokens: Dict[int, int]):
        """Feed one token per sequence through a single jitted step and
        commit the appends.  Returns (seq_ids, logits [B, V]) — one
        device array, so callers sample with one transfer."""
        seqs = list(tokens)
        page_table, lengths = self._plan_step(seqs)
        try:
            self._set_rows(seqs)
            toks = np.zeros((lengths.shape[0],), np.int32)
            toks[:len(seqs)] = [tokens[s] for s in seqs]
            logits, state = self._decode_jit(
                self.params, self.store.device_state(),
                page_table, lengths, jnp.asarray(toks))
            self.store.adopt(state)
            for s in seqs:
                self.table.commit_append(s)
        except Exception:
            self._recover_store()
            raise
        finally:
            self.table.unpin_all()
        return seqs, logits[:len(seqs)]

    def step(self, tokens: Dict[int, int]) -> Dict[int, jnp.ndarray]:
        """Dict-shaped wrapper of :meth:`step_batch`:
        returns {seq_id: logits [V]}."""
        seqs, logits = self.step_batch(tokens)
        return {s: logits[i] for i, s in enumerate(seqs)}

    def step_reference(self, tokens: Dict[int, int]) -> jnp.ndarray:
        """Unjitted reference of one decode step on the *seed* schedule:
        Python loop over layers, per-layer param slicing, one eager
        scalar append per sequence, per-layer page-table rebuild.  Does
        NOT commit — used for equivalence tests and as the benchmark
        baseline.  Returns logits [B, V] in ``tokens`` order."""
        cfg = self.cfg
        if self.has_state:
            raise NotImplementedError("the eager reference step serves "
                                      "attention-only models")
        seqs = list(tokens)
        try:
            rows = [self.table.prepare_append(s) for s in seqs]
            lengths = [self.table.length(s) for s in seqs]
            pos = jnp.asarray([[l] for l in lengths], jnp.int32)
            b = len(seqs)
            toks = jnp.asarray([tokens[s] for s in seqs], jnp.int32)
            new_lengths = jnp.asarray([l + 1 for l in lengths], jnp.int32)
            h = L.embed_tokens(self.params["embed"], toks[:, None],
                               self.dtype)
            for li in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[li], self.params["layers"])
                # this layer's pages as a one-layer stack
                st = jax.tree.map(lambda a: a[None],
                                  self.store.layer_state(li))
                q, k, v = self._attn_inputs(lp, h, pos)
                # seed schedule: one scalar append per sequence
                for bi, (l, row) in enumerate(zip(lengths, rows)):
                    st = self._append_state(
                        st, 0, jnp.asarray([row[l // self.page]], jnp.int32),
                        jnp.asarray([l % self.page], jnp.int32),
                        k[bi:bi + 1, 0], v[bi:bi + 1, 0])
                st = jax.tree.map(lambda a: a[0], st)
                # seed schedule: page table rebuilt per layer
                max_pages = max(len(r) for r in rows)
                page_table = jnp.asarray(
                    [r + [0] * (max_pages - len(r)) for r in rows],
                    jnp.int32)
                if self.quantized:
                    # pure-jnp dequantizing oracle — the reference the
                    # fused-dequant Pallas kernel is held to (<=1e-4)
                    o = kref.paged_attention_q8_ref(
                        q[:, 0].astype(self.dtype), st["k"], st["v"],
                        st["ks"], st["vs"], page_table, new_lengths)
                else:
                    o = ops.paged_attention(q[:, 0].astype(self.dtype),
                                            st["k"], st["v"], page_table,
                                            new_lengths)
                h = self._attn_out_ffn(lp, h, o.reshape(b, 1, -1))
            h = L.apply_norm(self.params["final_norm"], h, cfg.norm)
            logits = L.unembed(self.params["embed"],
                               self.params.get("lm_head"), h,
                               cfg.tie_embeddings)[:, 0]
        finally:
            self.table.unpin_all()
        return logits

    # -- one committed horizon batch ------------------------------------------

    def _plan_horizon(self, seqs: List[int], budgets: Dict[int, int]):
        """Host-side page management for one fused horizon: reserve + pin
        every page the horizon can touch (``reserve_horizon``), then
        build the padded device inputs.  Shapes are bucketed to powers
        of two, so horizons over 3 and 4 active sequences share one
        compiled program.  Also returns the attention grid's counts:
        ``bucket_rows`` x ``table_width`` page slots, of which ``pages``
        hold (or will hold) KV, and the kernel's ``block_grid`` of
        ``bucket_rows`` x blocks of ``pages_per_block`` pages, of which
        ``blocks`` hold KV."""
        try:
            rows = [self.table.reserve_horizon(s, budgets[s]) for s in seqs]
        except Exception:
            # a failed reservation (e.g. pinned working set overflow on a
            # later sequence) must not leave earlier sequences' data-less
            # reserved pages resident: roll every reservation back to the
            # committed lengths before re-raising
            for s in seqs:
                self.table.commit_horizon(s, 0)
            self.table.unpin_all()
            raise
        lengths = [self.table.length(s) for s in seqs]
        pps = _pow2(max(len(r) for r in rows))
        b2 = _pow2(len(seqs))
        table = np.zeros((b2, pps), np.int32)
        for i, r in enumerate(rows):
            table[i, :len(r)] = r
        lens = np.zeros((b2,), np.int32)
        lens[:len(seqs)] = lengths
        buds = np.zeros((b2,), np.int32)
        buds[:len(seqs)] = [budgets[s] for s in seqs]
        ppb = pages_per_block(pps, self.page)
        grid = {"bucket_rows": b2, "table_width": pps,
                "pages": sum(len(r) for r in rows),
                "blocks": sum(-(-len(r) // ppb) for r in rows),
                "block_grid": b2 * -(-pps // ppb)}
        return (jnp.asarray(table), jnp.asarray(lens), jnp.asarray(buds),
                grid)

    @staticmethod
    def _stream_ids(seqs, b2: int):
        """[b2] int32 per-row sampling-stream ids: the sequence id,
        stable across requeue/re-prefill and independent of batch slot
        — the coordinate that makes sampled draws failover-
        reproducible (padding rows never sample; any id works)."""
        streams = np.zeros((b2,), np.int32)
        streams[:len(seqs)] = [int(s) & 0x7FFFFFFF for s in seqs]
        return jnp.asarray(streams)

    def horizon_batch(self, tokens: Dict[int, int],
                      budgets: Dict[int, int], horizon: int,
                      eos_id: Optional[int] = None,
                      sampling: Optional[SamplingConfig] = None,
                      _key=None) -> Dict[int, List[int]]:
        """Run one fused decode horizon over ``tokens`` ({seq: pending
        token}) and commit the appends.  ``budgets[s]`` caps how many
        tokens sequence ``s`` may produce (<= horizon); ``eos_id`` stops
        a sequence on device when it emits that token.  ``sampling``
        selects on-device greedy argmax (default) or temperature/top-p
        Gumbel sampling; ``_key`` overrides the pass PRNG key (the
        ``decode`` loop threads one per pass).  Returns
        {seq_id: emitted tokens} — one device->host transfer total.

        The traced horizon length is bucketed DOWN to a power of two
        (the ``decode`` loop covers the rest with further — smaller —
        pow2 horizons), so mixed tails neither retrace the program nor
        burn masked full-model steps.
        """
        sampling = sampling or GREEDY
        seqs = list(tokens)
        if _key is None:
            _key = jax.random.PRNGKey(sampling.seed)
        h_run = _pow2_floor(min(horizon, max(budgets[s] for s in seqs)))
        with tracing.span("server.horizon") as counts:
            with tracing.span("server.horizon.plan"):
                page_table, lengths, buds, grid = self._plan_horizon(
                    seqs, {s: min(budgets[s], h_run) for s in seqs})
            counts.update(grid)
            try:
                with tracing.span("server.horizon.dispatch"):
                    self._set_rows(seqs)
                    toks = np.zeros((lengths.shape[0],), np.int32)
                    toks[:len(seqs)] = [tokens[s] for s in seqs]
                    eos = np.int32(eos_id if eos_id is not None else -1)
                    emitted, _, state = self._horizon_jit(
                        self.params, self.store.device_state(),
                        page_table, lengths, jnp.asarray(toks), buds,
                        jnp.asarray(eos), _key,
                        jnp.float32(sampling.temperature),
                        jnp.float32(sampling.top_p),
                        self._stream_ids(seqs, lengths.shape[0]),
                        horizon=h_run)
                with tracing.span("server.horizon.wait"):
                    # THE one transfer of the horizon: [h_run, B] int32
                    # tokens
                    emitted = np.asarray(emitted)
                with tracing.span("server.horizon.commit"):
                    self.store.adopt(state)
                    out = {}
                    for i, s in enumerate(seqs):
                        got = [int(t) for t in emitted[:, i] if t >= 0]
                        out[s] = got
                        if s in self._history:
                            self._history[s].extend(got)
                        # committed appends == emitted tokens (each fused
                        # step feeds one token and emits one); rollback
                        # the unused tail of the reservation
                        self.table.commit_horizon(s, len(got))
                    if self.has_state:
                        # each emitted token updated its row's state once
                        counts["state_rows"] = sum(len(g)
                                                   for g in out.values())
            except Exception:
                self._recover_store()
                # store intact (the failure was not a donated-buffer
                # loss): roll back every surviving sequence's unused
                # reservation so no data-less pages stay resident
                for s in seqs:
                    if s in self._seqs:
                        self.table.commit_horizon(s, 0)
                raise
            finally:
                self.table.unpin_all()
        return out

    # -- one committed speculative pass ---------------------------------------

    def _host_can_draft(self, seq_id: int) -> bool:
        """Host-side mirror of the device drafter's match predicate:
        does the lookup window contain an earlier occurrence of the
        history's final ``SPEC_MIN_MATCH``-gram?  Used only for the
        adaptive fallback — when NO live sequence can draft, a
        speculative pass would burn an H-position forward for one token
        each, so the pass routes through the plain fused horizon
        instead."""
        h = self._history.get(seq_id)
        if h is None or len(h) < SPEC_MIN_MATCH + 1:
            return False
        a = np.asarray(h[-self.spec_lookup_window:], np.int64)
        if a.shape[0] < SPEC_MIN_MATCH + 1:
            return False
        m = np.ones((a.shape[0] - SPEC_MIN_MATCH,), bool)
        for j in range(SPEC_MIN_MATCH):
            lo, hi = SPEC_MIN_MATCH - 1 - j, a.shape[0] - 1 - j
            m &= a[lo:hi] == a[-1 - j]
        return bool(m.any())

    def spec_horizon_batch(self, tokens: Dict[int, int],
                           budgets: Dict[int, int], horizon: int,
                           eos_id: Optional[int] = None,
                           sampling: Optional[SamplingConfig] = None,
                           _key=None) -> Dict[int, List[int]]:
        """Run one speculative draft-verify pass (arguments as
        :meth:`horizon_batch`) and commit the accepted prefixes.

        The reservation is the same ``reserve_horizon`` ask the plain
        horizon makes; ``commit_horizon`` keeps only the accepted
        tokens + bonus and rolls the rejected tail's pages back, so
        accepted-length variance never changes device shapes (the jit
        cache is keyed on the pow2 horizon/batch/table buckets only).
        Two adaptive fallbacks hold adversarial (non-repetitive)
        workloads near plain-horizon throughput, both counted in
        ``spec_stats``: when no live sequence's history can produce a
        draft — or the bucketed horizon degenerates below 2 — the pass
        routes to :meth:`horizon_batch`; and when the rolling
        acceptance-rate EMA drops below ``spec_alpha_floor`` the gate
        closes and only every ``spec_probe_every``-th pass still
        speculates (a probe — if the workload turns repetitive the EMA
        recovers and the gate reopens).
        """
        self._refuse_speculation()
        sampling = sampling or GREEDY
        seqs = list(tokens)
        if _key is None:
            _key = jax.random.PRNGKey(sampling.seed)
        h_run = _pow2_floor(min(horizon, max(budgets[s] for s in seqs)))
        gated = self.spec_alpha_ema < self.spec_alpha_floor
        if gated:
            self._spec_probe_tick += 1
        if (h_run < 2 or
                (gated and self._spec_probe_tick % self.spec_probe_every)
                or not any(self._host_can_draft(s) for s in seqs)):
            self.spec_stats["fallback_passes"] += 1
            if gated:
                self.spec_stats["gated_passes"] += 1
            return self.horizon_batch(tokens, budgets, horizon,
                                      eos_id=eos_id, sampling=sampling,
                                      _key=_key)
        page_table, lengths, buds, _ = self._plan_horizon(
            seqs, {s: min(budgets[s], h_run) for s in seqs})
        b2 = int(lengths.shape[0])
        w = self.spec_lookup_window
        hists = [self._history.get(s, [])[-w:] for s in seqs]
        # fixed-width table (pow2 of the lookup window): history growth
        # must never retrace mid-run, and the upload is a few KB anyway
        t2 = _pow2(w)
        hist = np.full((b2, t2), -1, np.int32)
        hlen = np.zeros((b2,), np.int32)
        for i, hh in enumerate(hists):
            hist[i, :len(hh)] = hh
            hlen[i] = len(hh)
        try:
            toks = np.zeros((b2,), np.int32)
            toks[:len(seqs)] = [tokens[s] for s in seqs]
            eos = np.int32(eos_id if eos_id is not None else -1)
            packed, state = self._spec_jit(
                self.params, self.store.device_state(), page_table,
                lengths, jnp.asarray(toks), buds, jnp.asarray(eos),
                jnp.asarray(hist), jnp.asarray(hlen), _key,
                jnp.float32(sampling.temperature),
                jnp.float32(sampling.top_p),
                self._stream_ids(seqs, b2), horizon=h_run)
            # THE one transfer of the pass: [h_run + 1, B] int32
            # (emitted rows + the drafted-count telemetry row)
            packed = np.asarray(packed)
            self.store.adopt(state)
            emitted, n_drafted = packed[:-1], packed[-1]
            out = {}
            st = self.spec_stats
            st["passes"] += 1
            for i, s in enumerate(seqs):
                got = [int(t) for t in emitted[:, i] if t >= 0]
                out[s] = got
                if s in self._history:
                    self._history[s].extend(got)
                # committed appends == accepted prefix + bonus; the
                # rejected tail of the reservation rolls back here
                self.table.commit_horizon(s, len(got))
                drafted = int(n_drafted[i])
                st["drafted"] += drafted
                st["accepted"] += max(0, min(len(got) - 1, drafted))
                st["emitted"] += len(got)
                hist_k = len(got)
                st["accepted_len_hist"][hist_k] = \
                    st["accepted_len_hist"].get(hist_k, 0) + 1
            # rolling acceptance EMA drives the adaptive gate: a pass
            # whose drafts mostly miss pushes the EMA toward closing it
            pass_drafted = int(n_drafted[:len(seqs)].sum())
            if pass_drafted:
                pass_acc = sum(
                    max(0, min(len(out[s]) - 1, int(n_drafted[i])))
                    for i, s in enumerate(seqs)) / pass_drafted
                # fast EMA: a hostile workload must close the gate
                # within a couple of failed passes, not a dozen
                self.spec_alpha_ema = (0.5 * self.spec_alpha_ema +
                                       0.5 * pass_acc)
        except Exception:
            self._recover_store()
            # store intact (the failure was not a donated-buffer loss):
            # roll back every surviving sequence's unused reservation so
            # no data-less pages stay resident
            for s in seqs:
                if s in self._seqs:
                    self.table.commit_horizon(s, 0)
            raise
        finally:
            self.table.unpin_all()
        return out

    def _refuse_speculation(self) -> None:
        if self.has_state:
            raise ValueError(
                "speculative decoding cannot serve a model with recurrent "
                "layers: a rejected draft would have to roll the state "
                "back; " + NO_STATE_SNAPSHOTS)

    def speculation_stats(self) -> Dict[str, object]:
        """Speculative telemetry: pass/fallback counts, drafted vs
        accepted candidates (``alpha`` = acceptance rate), and the
        emitted-length histogram {tokens_per_pass: passes}."""
        st = dict(self.spec_stats)
        st["accepted_len_hist"] = dict(st["accepted_len_hist"])
        st["alpha"] = (st["accepted"] / st["drafted"]
                       if st["drafted"] else 0.0)
        return st

    def reset_speculation_stats(self) -> None:
        """Zero the speculative counters and reopen the adaptive gate
        (EMA back to its optimistic start) — benchmark reps and tests
        that re-admit sequences on a warm server call this so one rep's
        acceptance history never gates the next."""
        self.spec_stats = {
            "passes": 0, "fallback_passes": 0, "gated_passes": 0,
            "drafted": 0, "accepted": 0, "emitted": 0,
            "accepted_len_hist": {}}
        self.spec_alpha_ema = 1.0
        self._spec_probe_tick = 0

    # -- decode loop ----------------------------------------------------------

    def decode(self, n_tokens: int, greedy: Optional[bool] = None,
               seqs: Optional[List[int]] = None, *,
               horizon: Optional[int] = None,
               eos_id: Optional[int] = None,
               budgets: Optional[Dict[int, int]] = None,
               sampling: Optional[SamplingConfig] = None,
               speculative: bool = False) -> Dict[int, list]:
        """Batched decode across live sequences (or a subset — the
        HBM window only needs to hold the *active* batch's working set;
        idle sequences spill to the flash tier).

        ``horizon=None`` is the per-token path: one host interaction
        (plan, jitted step, argmax transfer) per generated token.
        ``horizon=H`` runs the fused path: H tokens per host
        interaction, greedy outputs token-for-token identical.
        ``speculative=True`` runs draft-verify passes on the fused
        scaffold (defaults ``horizon`` to 8): up to H tokens per model
        forward, greedy outputs still token-identical.
        ``budgets``/``eos_id`` stop individual sequences early on both
        paths (on device inside a fused horizon; host-side between
        per-token steps); a sequence's entry stops growing once its
        budget is spent or it emits ``eos_id``.

        ``sampling`` is the token-selection config (``GREEDY`` when
        omitted).  ``greedy=`` is deprecated: it was the only selection
        switch before on-device sampling existed and survives as a
        shim."""
        if greedy is not None:
            warnings.warn(
                "decode(greedy=) is deprecated; pass "
                "sampling=SamplingConfig(temperature=...) instead",
                DeprecationWarning, stacklevel=2)
            if not greedy and sampling is None:
                raise ValueError(
                    "greedy=False no longer selects a sampler; pass "
                    "sampling=SamplingConfig(temperature=..., top_p=...)")
        sampling = sampling or GREEDY
        if speculative:
            self._refuse_speculation()
            if horizon is None:
                horizon = 8
            if horizon < 2:
                raise ValueError("speculative decoding needs horizon >= 2 "
                                 "(one fed token + >=1 draft candidate)")
        elif not sampling.greedy and horizon is None:
            # on-device sampling lives in the fused scaffold; run it at
            # H=1 (the per-token path's host argmax can't sample)
            horizon = 1
        active = self._seqs if seqs is None else seqs
        out = {s: [] for s in active}
        # page-in overlap model: pull any spilled pages of the activating
        # batch before the token loop starts
        for s in active:
            self.table.prefetch(s)
        # continue from the tokens pending after prefill
        cur = {s: self._pending.get(s, 0) for s in active}
        remaining = {s: min(n_tokens, budgets[s]) if budgets else n_tokens
                     for s in active}
        live = [s for s in active if remaining[s] > 0]
        if horizon is None:
            # per-token path: eos/budget stopping happens host-side (a
            # finished sequence leaves the batch and is never fed again
            # — the same append/commit trajectory as the fused path)
            while live:
                seqs, logits = self.step_batch({s: cur[s] for s in live})
                # one batched argmax + one device->host transfer per
                # token, not one per sequence
                nxt_arr = np.asarray(jnp.argmax(logits, axis=-1))
                for i, s in enumerate(seqs):
                    cur[s] = int(nxt_arr[i])
                    out[s].append(cur[s])
                    if s in self._history:
                        self._history[s].append(cur[s])
                    remaining[s] -= 1
                    if eos_id is not None and cur[s] == eos_id:
                        remaining[s] = 0
                live = [s for s in live if remaining[s] > 0]
            self._pending.update(cur)
            return out
        # ONE key from the sampling seed for every pass: draws are
        # keyed per (sequence id, absolute position) inside the device
        # program, so the key must NOT vary per pass — a requeued
        # sequence resuming mid-stream on another node (different pass
        # index, different batch) still re-derives the same draws
        base_key = jax.random.PRNGKey(sampling.seed)
        batch_fn = (self.spec_horizon_batch if speculative
                    else self.horizon_batch)
        while live:
            got = batch_fn(
                {s: cur[s] for s in live},
                {s: remaining[s] for s in live},
                min(horizon, max(remaining[s] for s in live)),
                eos_id=eos_id, sampling=sampling, _key=base_key)
            for s in live:
                out[s].extend(got[s])
                remaining[s] -= len(got[s])
                if got[s]:
                    cur[s] = got[s][-1]
                if eos_id is not None and got[s] and got[s][-1] == eos_id:
                    remaining[s] = 0          # stopped on device
            live = [s for s in live if remaining[s] > 0]
        self._pending.update(cur)
        return out

    # -- telemetry -----------------------------------------------------------

    def tier_stats(self) -> Dict[str, int]:
        agg = dict(vars(self.table.stats))
        agg["residency"] = self.table.residency()
        # dtype-aware: bytes counters already price quantized pages at
        # their code+scale size; expose the per-page constant and the
        # total tier traffic for the analytical model's wire/tier terms
        agg["page_bytes"] = self.store.page_bytes()
        agg["kv_bytes_moved"] = agg["bytes_in"] + agg["bytes_out"]
        return agg
