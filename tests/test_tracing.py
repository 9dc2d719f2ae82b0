"""Host spans of the serving loop (``repro.runtime.tracing``): the tree
one scheduler iteration opens, the counts it records, the bounded log,
and the same names on a profiler's host plane."""
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models.api import get_model
from repro.runtime import tracing
from repro.runtime.scheduler import ContinuousBatcher, Request
from repro.runtime.serve import PagedServer


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_arch("granite_3_2b").reduced(),
                              n_layers=2, vocab_size=64)
    model = get_model(cfg, compute_dtype=jnp.float32)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _batcher(tiny, **kw):
    _, model, params = tiny
    server = PagedServer(model, params, page_size=4, hbm_pages=64,
                         dtype=jnp.float32)
    return ContinuousBatcher(server, max_active=4, horizon=8, **kw)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n,
                                                dtype=np.int32)


def _traced(fn):
    """``fn()`` and the span records it closed."""
    t0 = time.monotonic()
    fn()
    return tracing.records(t0, time.monotonic())


def _tree(recs, parent=None):
    """[(name, children)] in opening order."""
    return [(r.name, _tree(recs, r.seq))
            for r in sorted(recs, key=lambda r: r.seq) if r.parent == parent]


def test_one_iteration_opens_the_span_tree(tiny):
    cfg = tiny[0]
    b = _batcher(tiny, prefill_chunk=8)
    # one request: its prompt fits one chunk, and 1 + 8 tokens finish
    # in the iteration's one horizon
    b.submit(Request(rid=7, prompt=_prompt(cfg, 6, 0), max_tokens=9))
    recs = _traced(b.step)
    assert _tree(recs) == [("scheduler.iteration", [
        ("scheduler.admit", [("server.prefill", [
            ("server.prefill.plan", []), ("server.prefill.dispatch", []),
            ("server.prefill.wait", [])])]),
        ("scheduler.retire", []),
        ("server.horizon", [
            ("server.horizon.plan", []), ("server.horizon.dispatch", []),
            ("server.horizon.wait", []), ("server.horizon.commit", [])]),
        ("scheduler.retire", [])])]
    by = {r.name: r for r in recs}
    assert by["server.prefill"].counts == {"tokens": 6, "final": True}
    hz = by["server.horizon"].counts
    assert set(hz) == {"pages", "bucket_rows", "table_width", "blocks",
                       "block_grid"}
    # 6 prompt tokens + 8 horizon steps on pages of 4, one block of the
    # kernel's grid
    assert hz["pages"] == 4 <= hz["bucket_rows"] * hz["table_width"]
    assert hz["blocks"] == 1 <= hz["block_grid"] == hz["bucket_rows"]
    for name in ("scheduler.iteration", "scheduler.admit",
                 "scheduler.retire"):
        assert all(r.counts == {} for r in recs if r.name == name)
    for r in recs:
        assert r.start <= r.end


def test_counts_match_what_the_server_did(tiny):
    cfg = tiny[0]
    b = _batcher(tiny, prefill_chunk=4)
    for i, (n, g) in enumerate([(6, 9), (11, 5), (3, 12), (9, 17)]):
        b.submit(Request(rid=i, prompt=_prompt(cfg, n, i), max_tokens=g))
    recs = _traced(b.run_to_completion)
    pre = [r.counts for r in recs if r.name == "server.prefill"]
    assert sum(c["tokens"] for c in pre) == b.server.prefill_tokens_computed
    assert sum(c["final"] for c in pre) == 4
    hz = [r.counts for r in recs if r.name == "server.horizon"]
    assert hz
    for c in hz:
        assert 0 < c["pages"] <= c["bucket_rows"] * c["table_width"]
        assert 0 < c["blocks"] <= c["block_grid"] <= c["bucket_rows"] * \
            c["table_width"]


def test_log_drops_the_oldest_and_counts_them():
    rec = tracing.Recorder(capacity=4)
    t0 = time.monotonic()
    for i in range(6):
        with rec.span("server.horizon", i=i):
            pass
    t1 = time.monotonic()
    assert rec.dropped == 2
    assert rec.records(t0, t1) is None       # the window lost its head
    kept = rec.records(rec._log[0].start, t1)
    assert [r.counts["i"] for r in kept] == [2, 3, 4, 5]
    assert rec.records(t1 + 1, t1 + 2) == []


def test_spans_appear_on_the_profilers_host_plane(tiny, tmp_path):
    from jax.profiler import ProfileData
    cfg = tiny[0]
    b = _batcher(tiny, prefill_chunk=8)
    b.submit(Request(rid=1, prompt=_prompt(cfg, 6, 3), max_tokens=9))
    b.step()                               # compiled outside the trace
    b.submit(Request(rid=2, prompt=_prompt(cfg, 6, 4), max_tokens=9))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        recs = _traced(b.step)
    path = next(os.path.join(d, n) for d, _, names in os.walk(tmp_path)
                for n in names if n.endswith(".xplane.pb"))
    host = {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events}
    names = {r.name for r in recs}
    assert "server.horizon.wait" in names
    assert names <= host
