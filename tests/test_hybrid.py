"""Hybrid Mamba-2 / attention serving (granite-4.0-h-micro's layer
pattern at a reduced size) against the plain reference.

``PagedServer`` holds the attention layers' KV in pages and each
sequence's Mamba-2 state in a slot beside them.  Prefill (chunked SSD
from the slot) and decode (the fused horizon) must reproduce the
float32 reference's logits (``bench/ref_hybrid.py``: the recurrence
token by token), padding and budget cuts must leave a slot as an
unpadded, uncut run leaves it, a freed slot comes back zeroed, and what
cannot carry the state yet refuses the model.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import ref_hybrid, weights_hybrid                   # noqa: E402
from bench.drivers import serve_hybrid                         # noqa: E402
from repro.kernels.ssm_state import ssm_state_update           # noqa: E402
from repro.models.api import get_model                         # noqa: E402
from repro.models.mamba2 import ssd_step                       # noqa: E402
from repro.runtime.scheduler import ContinuousBatcher, Request  # noqa: E402
from repro.runtime.serve import PagedServer                    # noqa: E402

# granite-4.0-h-micro's configuration file at a reduced size: two periods
# of (mamba, mamba, attention, mamba) at d_model 64, SSD chunks of 8
HF = dict(json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "granite-4.0-h-micro.json"))),
          num_hidden_layers=8, hidden_size=64, num_attention_heads=4,
          num_key_value_heads=2, intermediate_size=128, vocab_size=128,
          mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
          mamba_chunk_size=8,
          layer_types=["mamba", "mamba", "attention", "mamba"] * 2)
CFG = serve_hybrid.arch_config(HF)
PAGE = 4


@pytest.fixture(scope="module")
def model_params():
    model = get_model(CFG, compute_dtype=jnp.float32)
    return model, weights_hybrid.make(HF, 7, jnp.float32)


def _server(model_params, **kw):
    model, params = model_params
    kw.setdefault("state_slots", 4)
    return PagedServer(model, params, page_size=PAGE, hbm_pages=64,
                       dtype=jnp.float32, **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n,
                                                dtype=np.int32)


def _ref(model_params, tokens):
    return np.asarray(ref_hybrid.forward_logits(
        HF, model_params[1], np.asarray(tokens, np.int32), bucket=8))


def _assert_served(model_params, prompt, served):
    """Every served token is the reference's argmax at its position."""
    ref = _ref(model_params, np.concatenate([prompt, served[:-1]]))
    rows = ref[len(prompt) - 1:len(prompt) - 1 + len(served)]
    gaps = rows.max(-1) - rows[np.arange(len(served)), served]
    assert gaps.max() < 1e-4, gaps


def _slot_state(srv, seq):
    st = srv.store.device_state()
    sl = srv.table.slot(seq)
    return np.asarray(st["ssm"][:, sl]), np.asarray(st["conv"][:, sl])


def test_weights_layout_is_the_models(model_params):
    model, params = model_params
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert [w.shape for w in jax.tree.leaves(want)] == \
        [p.shape for p in jax.tree.leaves(params)]


def test_prefill_then_horizon_decode_matches_reference(model_params):
    """Prompts longer than one chunk (the last chunk padded to a power
    of two), then decode through the fused horizon with a budget that
    cuts one row inside a horizon: every served token is the
    reference's argmax, and each prefill's logits are the reference's."""
    srv = _server(model_params)
    prompts = {0: _prompt(21, 1), 1: _prompt(11, 2), 2: _prompt(6, 3)}
    for s, p in prompts.items():
        logits = srv.add_request(s, p, chunk=8)
        np.testing.assert_allclose(np.asarray(logits),
                                   _ref(model_params, p)[len(p) - 1],
                                   rtol=1e-4, atol=1e-4)
    first = srv.pending_tokens()
    out = srv.decode(9, horizon=4, budgets={0: 9, 1: 3, 2: 6})
    for s, p in prompts.items():
        _assert_served(model_params, p, [first[s]] + out[s])


def test_decode_logits_match_reference(model_params):
    """The per-token step (the horizon at H=1) returns the reference's
    logits at every position."""
    srv = _server(model_params)
    p = _prompt(13, 4)
    srv.add_request(0, p, chunk=8)
    toks = [srv.pending_tokens()[0]]
    got = []
    for _ in range(5):
        lg = srv.step({0: toks[-1]})[0]
        got.append(np.asarray(lg))
        toks.append(int(np.argmax(got[-1])))
    ref = _ref(model_params, np.concatenate([p, toks[:-1]]))
    np.testing.assert_allclose(np.stack(got), ref[len(p):len(p) + 5],
                               rtol=1e-4, atol=1e-4)


def test_padded_chunks_leave_the_unpadded_state(model_params):
    """Pitfall (a): prefill chunks padded to a power of two must not
    move the state — dt is 0 at the padding and the conv tail is taken
    at the true length."""
    p = _prompt(16, 5)
    a, b = _server(model_params), _server(model_params)
    a.add_request(0, p)                       # one chunk of 16: no padding
    b.add_request(0, p, chunk=5)              # 5, 5, 5, 1: padded to 8
    for x, y in zip(_slot_state(a, 0), _slot_state(b, 0)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


def test_budget_cut_row_keeps_its_state(model_params):
    """Pitfall (b): a row whose budget ends inside a horizon keeps the
    state it had after its last token, as a run that stops there does;
    decoding it further then stays on the reference."""
    p, q = _prompt(9, 6), _prompt(7, 7)
    cut, alone = _server(model_params), _server(model_params)
    for srv in (cut, alone):
        srv.add_request(0, p)
    pending = alone.pending_tokens()[0]
    cut.add_request(1, q)
    cut.decode(8, horizon=8, budgets={0: 3, 1: 8})      # row 0 cut at 3
    first = alone.decode(3, horizon=2)[0]               # 2 + 1, no cut
    for x, y in zip(_slot_state(cut, 0), _slot_state(alone, 0)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)
    more = cut.decode(4, seqs=[0], horizon=4)[0]
    _assert_served(model_params, p, [pending] + first + more)


def test_freed_slot_comes_back_zeroed(model_params):
    srv = _server(model_params, state_slots=1)
    srv.add_request(0, _prompt(10, 8))
    slot = srv.table.slot(0)
    assert np.abs(_slot_state(srv, 0)[0]).max() > 0
    srv.free_sequence(0)
    assert srv.table.free_slots == 1
    srv.begin_request(1, _prompt(5, 9))
    assert srv.table.slot(1) == slot
    for a in _slot_state(srv, 1):
        assert not a.any()


def test_admission_waits_for_a_state_slot(model_params):
    """Two slots, four requests: the scheduler admits two at a time and
    every request finishes with its budget."""
    srv = _server(model_params, state_slots=2)
    b = ContinuousBatcher(srv, max_active=4, horizon=4, prefill_chunk=8)
    for i in range(4):
        b.submit(Request(i, _prompt(6 + i, 10 + i), max_tokens=13))
    most = 0
    for _ in range(200):
        if not (b.waiting or b.prefilling or b.active):
            break
        b.step()
        most = max(most, len(b.active) + len(b.prefilling))
    assert most == 2
    assert sorted(len(r.output) for r in b.finished) == [13] * 4


def test_what_cannot_carry_the_state_refuses(model_params):
    """(c) the prefix cache, (d) speculation, the pool and preemption to
    the flash tier each raise, naming the missing snapshot work."""
    model, params = model_params
    with pytest.raises(ValueError, match="snapshots"):
        _server(model_params, prefix_cache=True)
    srv = _server(model_params)
    assert not srv.prefix_cache
    srv.add_request(0, _prompt(6, 11))
    with pytest.raises(ValueError, match="snapshots"):
        srv.decode(4, horizon=4, speculative=True)
    from repro.runtime.pool import PoolServer
    with pytest.raises(ValueError, match="snapshots"):
        PoolServer(model, params, n_nodes=1)
    small = PagedServer(model, params, page_size=PAGE, hbm_pages=4,
                        dtype=jnp.float32, state_slots=2)
    small.add_request(0, _prompt(12, 12))
    with pytest.raises(RuntimeError, match="snapshots"):
        small.add_request(1, _prompt(8, 13))


def test_state_kernel_matches_ssd_step():
    """``ssm_state_update`` (interpret mode) equals ``ssd_step`` plus the
    D skip for live rows, writes their slots of the named layer in
    place, and leaves every other slot as it was."""
    n_layers, slots, h, dh, ds = 3, 5, 4, 8, 16
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(k[0], (n_layers, slots, h, dh, ds))
    rows = 4
    x = jax.random.normal(k[1], (rows, h, dh))
    dt = jax.nn.softplus(jax.random.normal(k[2], (rows, h)))
    A = -jnp.exp(jax.random.normal(k[3], (h,)))
    B = jax.random.normal(k[4], (rows, ds))
    C = jax.random.normal(k[5], (rows, ds))
    D = jax.random.normal(k[6], (h,))
    row_slots = jnp.asarray([2, -1, 0, 3], jnp.int32)
    y, got = jax.jit(lambda *a: ssm_state_update(*a, interpret=True))(
        state, x, dt, A, B, C, D, row_slots, jnp.int32(1))
    live = np.asarray(row_slots) >= 0
    want_y, new = ssd_step(x, dt, dt * A[None], B, C,
                           state[1, jnp.maximum(row_slots, 0)])
    want_y = want_y + x * D[None, :, None]
    np.testing.assert_allclose(np.asarray(y)[live],
                               np.asarray(want_y)[live], rtol=1e-5,
                               atol=1e-5)
    want = np.asarray(state).copy()
    for r in np.flatnonzero(live):
        want[1, int(row_slots[r])] = np.asarray(new[r])
    # the last slot is the spare the skipped row names
    np.testing.assert_allclose(np.asarray(got)[:, :slots - 1],
                               want[:, :slots - 1], rtol=1e-5, atol=1e-5)


def test_server_kernel_path_matches_jnp_path(model_params):
    """The chip's path — the state kernel and the paged kernel, here
    interpreted — emits the tokens of the jnp path."""
    outs = []
    for kernels in (False, True):
        srv = _server(model_params)
        srv._jnp_attention = not kernels
        for s in range(2):
            srv.add_request(s, _prompt(7 + 3 * s, 20 + s))
        outs.append(srv.decode(5, horizon=4, budgets={0: 5, 1: 2}))
    assert outs[0] == outs[1]


@pytest.fixture
def launcher(monkeypatch):
    """The serving launcher, leaving this process's compile cache off."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    return serve.main


@pytest.mark.parametrize("flags", [["--pool"],
                                   ["--paged", "--speculative",
                                    "--horizon", "4"]])
def test_launcher_refuses_what_cannot_carry_the_state(launcher, flags):
    with pytest.raises(SystemExit, match="snapshots"):
        launcher(["--arch", "granite-4.0-h-micro", "--reduced"] + flags)


def test_launcher_serves_the_hybrid_paged(launcher, capsys):
    launcher(["--arch", "granite-4.0-h-micro", "--reduced", "--paged",
          "--requests", "2", "--prompt-len", "9", "--gen", "5",
          "--horizon", "4", "--prefill-chunk", "8"])
    assert "served 2 requests, 10 tokens" in capsys.readouterr().out
