"""Serving driver for a hybrid Mamba-2 / attention model
(granite-4.0-h-micro): ``ContinuousBatcher.step()`` over ``PagedServer``
with the Mamba layers' state in per-sequence slots beside the KV pages.

The timed loop is ``bench/drivers/serve.py``'s: its ``warm`` (every
reachable horizon and prefill program once), ``Stamps``, ``drive``,
``sample`` and ``measure``.  What differs is the model: the program's
``ArchConfig`` from the configuration file (layer kinds, Mamba-2 sizes,
Granite's multipliers), the weights of ``bench/weights_hybrid.py``, a
server with ``state_slots`` from the cell file, and the check against
``bench/ref_hybrid.py``: ``served_gap_max`` is the widest gap by which a
served token's logit lies below the float32 reference's best.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import gen, ref_hybrid, session, weights_hybrid
from bench.drivers import serve
from bench.drivers.serve import (DTYPES, Stamps, _request, measure, sample,
                                 warm)


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    if cfg["hidden_act"] != "silu" or cfg.get("num_local_experts", 0):
        raise ValueError("serve_hybrid runs dense SwiGLU hybrids, got "
                         f"{cfg['hidden_act']!r} with "
                         f"{cfg.get('num_local_experts')} experts")
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != \
            cfg["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head must be "
                         "mamba_expand x hidden_size")
    return ArchConfig(
        name=cfg["name"], family="hybrid", block_type="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=nh,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=d // nh, tie_embeddings=cfg["tie_word_embeddings"],
        rope=cfg["position_embedding_type"] != "nope",
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm", act="swiglu",
        ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        layer_types=tuple(cfg["layer_types"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]))


class Served(serve.Served):
    """``serve.Served`` for the hybrid model: the same set-up, with its
    own configuration, weights and state slots."""

    def __init__(self, cell, seed: int, log):
        from repro.models.api import get_model
        from repro.runtime.scheduler import ContinuousBatcher
        from repro.runtime.serve import PagedServer

        c = cell.cell
        self.cell = cell
        self.counter = session.CompileCounter()
        t0 = time.monotonic()
        self.dtype = DTYPES[c["server"]["dtype"]]
        self.model = get_model(arch_config(cell.config),
                               compute_dtype=self.dtype)
        params = self.make_weights(seed)
        self.server = PagedServer(self.model, params, dtype=self.dtype,
                                  **{k: v for k, v in c["server"].items()
                                     if k != "dtype"})
        self.batcher = ContinuousBatcher(self.server, **c["scheduler"])
        self.n_programs = warm(self.server, cell)
        self.stamps = Stamps(self.batcher, self.server)
        req = _request(-1, np.arange(cell.mix["prompt"]["min"],
                                     dtype=np.int32),
                       1 + c["scheduler"]["horizon"])
        self.batcher.submit(req)
        while not req.done:
            self.batcher.step()
        self.reset()
        self.setup_s = time.monotonic() - t0
        self.setup_counts = self.counter.snapshot()
        log(f"setup {self.setup_s!r} s: weights "
            f"{sum(x.nbytes for x in jax.tree.leaves(params))} B, "
            f"{self.n_programs} programs warmed, {self.setup_counts}")

    def make_weights(self, seed: int):
        """Weights of ``seed``, checked against the program's layout."""
        self.wseed = gen.jax_seed(gen.seed_streams(seed)[2])
        params = weights_hybrid.make(self.cell.config, self.wseed,
                                     self.dtype)
        want = jax.eval_shape(lambda k: self.model.init(k, dtype=self.dtype),
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise ValueError("bench/weights_hybrid.py layout differs from "
                             "the program's parameters")
        return jax.block_until_ready(params)


def check(cell, pairs, wseed: int, dtype, log, control: bool = False,
          params=None):
    """The reference over the sampled (prompt, served) ``pairs``; with
    ``control`` also the int8 control's widest gap on them.  The
    reference makes its weights from ``wseed`` unless given them."""
    t0 = time.monotonic()
    if params is None:
        params = weights_hybrid.make(cell.config, wseed, dtype)
    gaps = [ref_hybrid.served_gaps(cell.config, params, p, o)
            for p, o in pairs]
    gap = float(max(g.max() for g in gaps)) if gaps else None
    n_tok = int(sum(len(o) for _, o in pairs))
    log(f"reference over {len(pairs)} requests, {n_tok} served tokens in "
        f"{time.monotonic() - t0!r} s: widest gap {gap!r}")
    out = {"served_gap_max": gap, "checked_tokens": n_tok}
    if control and pairs:
        out["control_int8_reference"] = float(max(
            ref_hybrid.control_gaps(cell.config, params, p, o).max()
            for p, o in pairs))
        log(f"int8 control: widest gap {out['control_int8_reference']!r}")
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, log,
        control: bool = False) -> dict:
    c = cell.cell
    S = Served(cell, seed, log)
    rec = measure(S, cell.mix, seed, seconds, lead=c["lead_s"],
                  drain=c["drain_s"], trace_s=c["trace_s"] if trace else None,
                  log=log)
    rec["setup_s"] = S.setup_s
    rec["memory_peak_bytes"] = session.memory_peak()
    pairs = sample(rec.pop("finished"), c["check"]["requests"], seed)
    wseed, dtype = S.wseed, S.dtype
    S.close()
    log(f"device bytes in use after freeing the program: "
        f"{session.bytes_in_use()}")
    got = check(cell, pairs, wseed, dtype, log, control=control)
    lim = c["check"]["limits"]
    rec["checks"] = {
        "served_gap_max": {"value": got["served_gap_max"],
                           "limit": lim["served_gap_max"]},
        "requests_unanswered": {"value": rec["unanswered"], "limit": 0}}
    rec["checked_tokens"] = got["checked_tokens"]
    rec["checked_pairs"], rec["wseed"] = pairs, wseed
    if "control_int8_reference" in got:
        rec["control_int8_reference"] = got["control_int8_reference"]
    return rec
