"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run at a tiny size on the CPU: once as it is (correct), once with a
token altered where it is produced (not correct)."""
import pytest

from bench import harness
from bench.tests.conftest import quiet

SEED = 2 ** 40 + 77


def _run(root, name, seconds=2.0):
    cell = harness.find_cell(name, root)
    rec = harness.driver_module(cell).run(cell, seed=SEED, seconds=seconds,
                                          trace=False, log=quiet)
    m = harness.compute_metrics(cell, "end_to_end", rec, None)
    return harness.result_line(rec, m, {}, None), rec


@pytest.mark.parametrize("name,metrics", [
    ("tiny-chat", {"setup_s", "output_tok_s"}),
    ("tiny-batch", {"setup_s", "output_tok_s"})])
def test_serving_sound_run_is_correct(tiny_root, name, metrics):
    out, rec = _run(tiny_root, name)
    assert out["correct"], out["checks"]
    assert rec["checked_tokens"] > 0
    assert rec["compiles_in_window"]["compiled"] == 0
    assert set(out["metrics"]) == metrics


def test_serving_altered_token_is_not_correct(tiny_root, monkeypatch):
    from repro.runtime.serve import PagedServer
    horizon = PagedServer.horizon_batch
    vocab = 128

    def altered(self, tokens, budgets, *a, **kw):
        out = horizon(self, tokens, budgets, *a, **kw)
        return {s: [(t + 1) % vocab for t in toks] for s, toks in out.items()}

    monkeypatch.setattr(PagedServer, "horizon_batch", altered)
    out, _ = _run(tiny_root, "tiny-chat")
    assert not out["correct"]
    assert out["checks"]["served_gap_max"]["value"] > \
        out["checks"]["served_gap_max"]["limit"]

