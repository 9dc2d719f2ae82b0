"""The hybrid (granite-4.0-h-micro) cell's files: its configuration maps
to the program's ``ArchConfig``, its weights to the model's layout, its
reference to a plain NumPy recurrence, its driver runs a tiny hybrid
cell correctly on the CPU while the int8 control and an altered token
fail the check, and its two readers compute what a recorded record
holds."""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, ref_hybrid, weights_hybrid
from bench.tests.conftest import LENGTHS, SERVE_CELL, build_root, quiet

CONFIG = json.load(open(os.path.join(harness.BENCH, "configs",
                                     "granite-4.0-h-micro.json")))
DRV = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                       "serve_hybrid.py"))
TINY = dict(CONFIG, name="tiny-hybrid", num_hidden_layers=8,
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, vocab_size=128, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
            layer_types=["mamba", "mamba", "attention", "mamba"] * 2)
CELL = dict(SERVE_CELL, driver="serve_hybrid", drain_s=0,
            server=dict(SERVE_CELL["server"], state_slots=4))
SEEDS = (2 ** 40 + 13, 7)


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


def test_config_maps_to_arch_config_key_for_key():
    from repro.configs.base import get_arch
    got = DRV.arch_config(CONFIG)
    assert got == dataclasses.replace(get_arch("granite-4.0-h-micro"),
                                      source="")
    assert CONFIG["reduced"] == []
    assert got.state_layers == 36 and got.attn_layers == 4


def test_weights_match_the_model_layout():
    from repro.models.api import get_model
    model = get_model(DRV.arch_config(TINY), compute_dtype=jnp.float32)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = weights_hybrid.make(TINY, 3, jnp.float32)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(w.shape, w.dtype) for w in jax.tree.leaves(want)] == \
        [(g.shape, g.dtype) for g in jax.tree.leaves(got)]


def test_reference_mamba_is_the_plain_recurrence():
    """``ref_hybrid``'s Mamba-2 mixer equals a NumPy loop over tokens."""
    p = jax.tree.map(lambda a: np.asarray(a[1], np.float64),
                     weights_hybrid.make(TINY, 5, jnp.float32)
                     ["mamba_layers"]["mamba"])
    t, d, di, ds, h, dh = 12, 64, 128, 16, 8, 16
    x = np.random.default_rng(0).normal(size=(t, d))
    got = np.asarray(ref_hybrid._mamba(TINY, False, jnp.asarray(
        x, jnp.float32), jax.tree.map(jnp.asarray, p)))

    def silu(v):
        return v / (1 + np.exp(-v))

    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * ds], \
        zxbcdt[:, 2 * di + 2 * ds:]
    conv = np.zeros_like(xbc)
    for i in range(t):
        for k in range(4):
            if i - 3 + k >= 0:
                conv[i] += p["conv_w"][k] * xbc[i - 3 + k]
    xbc = silu(conv + p["conv_b"])
    xs, bm, cm = xbc[:, :di].reshape(t, h, dh), xbc[:, di:di + ds], \
        xbc[:, di + ds:]
    dt = np.log1p(np.exp(dt + p["dt_bias"]))
    a = -np.exp(p["a_log"])
    state = np.zeros((h, dh, ds))
    y = np.zeros((t, h, dh))
    for i in range(t):
        state = (np.exp(dt[i] * a)[:, None, None] * state +
                 (dt[i][:, None] * xs[i])[:, :, None] * bm[i])
        y[i] = state @ cm[i] + p["d_skip"][:, None] * xs[i]
    y = y.reshape(t, di) * silu(z)
    y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) +
                    TINY["rms_norm_eps"]) * p["gate_norm"]["scale"]
    np.testing.assert_allclose(got, y @ p["out_proj"], rtol=1e-4,
                               atol=1e-4)


def test_weights_keep_the_model_from_copying_its_input():
    """With tied embeddings scaled by ``embedding_multiplier``, an
    embedding at N(0, 0.02^2) outweighs what the layers add, and each
    position's best token is its own input (greedy decoding repeats the
    prompt's last token, so no error turns a token).  The weights'
    embedding leaves that to chance."""
    cfg = dict(CONFIG, num_hidden_layers=2, hidden_size=512,
               num_attention_heads=8, num_key_value_heads=2,
               intermediate_size=1024, vocab_size=4096, mamba_n_heads=16,
               layer_types=["mamba", "attention"])
    ids = np.random.default_rng(0).integers(0, 4096, 256).astype(np.int32)
    params = weights_hybrid.make(cfg, 9, jnp.float32)

    def copied(p):
        best = np.asarray(ref_hybrid.forward_logits(cfg, p, ids))[:256]
        return float(np.mean(best.argmax(-1) == ids))

    assert copied(params) < 0.02
    # the same draw at N(0, 0.02^2)
    table = params["embed"]["table"] * cfg["embedding_multiplier"]
    assert copied(dict(params, embed={"table": table})) > 0.9


@pytest.fixture(scope="module")
def hybrid_cell(tmp_path_factory):
    """A tiny benchmark root whose new cell serves the tiny hybrid."""
    root = build_root(str(tmp_path_factory.mktemp("hybrid_root")))
    b = os.path.join(root, "bench")
    for obj, *path in ((TINY, "configs", "tiny-hybrid.json"),
                       (CELL, "cells", "tiny-hybrid.json"),
                       (dict(LENGTHS, arrivals={"kind": "closed",
                                                "clients": 4,
                                                "requests_per_client": 64}),
                        "mixes", "tiny-hybrid.json")):
        with open(os.path.join(b, *path), "w") as f:
            json.dump(obj, f)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny-hybrid", "source": "test",
                            "file": "bench/configs/tiny-hybrid.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-hybrid", "config": "tiny-hybrid",
                              "traffic": "tiny-hybrid", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "granite-h-micro-reasoning32" in m.get("workloads", ()):
            m["workloads"].append("tiny-hybrid")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return harness.find_cell("tiny-hybrid", root)


@pytest.fixture(scope="module")
def windows(hybrid_cell):
    """Sound windows of the tiny hybrid cell, one per seed, with their
    checks."""
    S = DRV.Served(hybrid_cell, SEEDS[0], quiet)
    out = []
    try:
        for seed in SEEDS:
            if seed != SEEDS[0]:
                S.server.params = S.make_weights(seed)
            S.reset()
            rec = DRV.measure(S, hybrid_cell.mix, seed, 2.0, lead=0.5,
                              drain=0, log=quiet)
            rec["setup_s"] = S.setup_s
            pairs = DRV.sample(rec.pop("finished"), 3, seed)
            got = DRV.check(hybrid_cell, pairs, S.wseed, S.dtype, quiet)
            out.append((rec, pairs, S.wseed, got))
    finally:
        S.close()
    return out


def test_sound_runs_pass_the_check(hybrid_cell, windows):
    limit = hybrid_cell.cell["check"]["limits"]["served_gap_max"]
    for rec, pairs, _, got in windows:
        assert rec["compiles_in_window"]["compiled"] == 0
        assert got["checked_tokens"] > 0
        assert got["served_gap_max"] <= limit, got
        m = harness.compute_metrics(hybrid_cell, "end_to_end", rec, None)
        assert set(m) == {"setup_s", "output_tok_s"}


@pytest.mark.parametrize("seed", [3, 11])
def test_int8_control_fails_the_gap_limit(hybrid_cell, seed):
    """Fixed prompts served greedily (no clock in the way): the program
    passes the cell's limit, the int8 reference on the same requests
    does not."""
    from repro.models.api import get_model
    from repro.runtime.serve import PagedServer
    cfg = hybrid_cell.config
    params = weights_hybrid.make(cfg, seed, jnp.float32)
    srv = PagedServer(get_model(DRV.arch_config(cfg),
                                compute_dtype=jnp.float32), params,
                      page_size=16, hbm_pages=64, dtype=jnp.float32,
                      state_slots=4)
    rng = np.random.default_rng(seed)
    prompts = {i: rng.integers(0, cfg["vocab_size"], 24 + 8 * i,
                               dtype=np.int32) for i in range(4)}
    for i, p in prompts.items():
        srv.add_request(i, p)
    first = srv.pending_tokens()
    out = srv.decode(47, horizon=8)
    pairs = [(p, np.asarray([first[i]] + out[i], np.int32))
             for i, p in prompts.items()]
    limit = hybrid_cell.cell["check"]["limits"]["served_gap_max"]
    prog = max(ref_hybrid.served_gaps(cfg, params, p, o).max()
               for p, o in pairs)
    ctrl = max(ref_hybrid.control_gaps(cfg, params, p, o).max()
               for p, o in pairs)
    assert prog <= limit < ctrl, (prog, ctrl)


def test_an_altered_token_fails_the_check(hybrid_cell, windows):
    _, pairs, wseed, _ = windows[0]
    (p, o), rest = pairs[0], pairs[1:]
    o = o.copy()
    o[len(o) // 2] = (o[len(o) // 2] + 1) % TINY["vocab_size"]
    got = DRV.check(hybrid_cell, [(p, o)] + rest, wseed, jnp.float32, quiet)
    assert got["served_gap_max"] > \
        hybrid_cell.cell["check"]["limits"]["served_gap_max"]


def test_readers_on_a_recorded_record():
    """The two readers over a record of one traced horizon: 3 rows at
    lengths 10, 20, 30 that emit 8, 8 and 5 tokens (21 row updates),
    a kernel time of 1 ms and a program time of 40 ms."""
    from repro.runtime import tracing
    t0 = time.monotonic()
    with tracing.span("server.horizon", state_rows=21):
        pass
    t1 = time.monotonic()
    rows = [(10, 8), (20, 8), (30, 5)]
    rec = {"window": (t0, t1), "trace_window": (t0, t1), "horizon": 8,
           "config": CONFIG, "horizons": [(t0, t1, rows)]}
    tr = {"device_kind": "TPU v5 lite",
          "modules": {"decode_horizon_step": 0.040},
          "breakdown": {"device_ops": [
              ["decode_horizon_step/ssm_state_update", 0.001]]}}
    state = 64 * 64 * 128
    nbytes = 21 * 36 * 4 * (2 * state + 2 * 64 * 64 + 2 * 64 + 2 * 128)
    got = _reader("ssm_state_roofline").compute(rec, tr)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.001)
    # per token: twice the matmul weights, attention over the context in
    # 4 layers, the state update and conv in 36
    d, f, v, di = 2048, 8192, 100352, 4096
    weights = (4 * (d * 48 * 64 + 32 * 64 * d) +
               36 * (d * (2 * di + 256 + 64) + di * d) + 40 * 3 * d * f +
               d * v)
    fixed = 2 * weights + 36 * (5 * state + 2 * 64 * 64 + 2 * 4 * 4352)
    ctx = sum(n + i + 1 for n, e in rows for i in range(e))
    ops = 21 * fixed + 4 * 32 * 64 * 4 * ctx
    got = _reader("hybrid_decode_mfu").compute(rec, tr)
    assert got == pytest.approx(100 * ops / (0.040 * 197e12))
    assert 0 < got < 100
