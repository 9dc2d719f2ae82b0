"""The readers of the program's span log (``iter_host_ms``,
``prefill_ms_per_ktok``, ``attn_page_fill``) over a tiny serving window
on the CPU, and the device trace's idle gaps landing on the innermost
program span."""
import math
import os
import sys
import time

import pytest
from jax.profiler import ProfileData

from bench import harness, spans, trace
from bench.tests.conftest import quiet
from bench.tests.test_trace import MS, _line, _plane

READERS = ("iter_host_ms", "prefill_ms_per_ktok", "attn_page_fill")
SEED = 2 ** 40 + 91


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


@pytest.fixture(scope="module")
def window(tiny_root):
    cell = harness.find_cell("tiny-batch", tiny_root)
    drv = harness.driver_module(cell)
    served = drv.Served(cell, SEED, quiet)
    try:
        return drv.measure(served, cell.mix, SEED, 2.0, lead=0.5, drain=0,
                           log=quiet)
    finally:
        served.close()


def test_readers_give_finite_values(window):
    got = {n: _reader(n).compute(window, None) for n in READERS}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in got.values()), got
    assert got["attn_page_fill"] <= 100


@pytest.mark.parametrize("reader", READERS)
def test_reader_finds_nothing_in_an_empty_window(window, reader):
    now = time.monotonic()
    assert _reader(reader).compute(dict(window, window=(now, now)),
                                   None) is None


@pytest.mark.parametrize("reader", READERS)
def test_reader_finds_nothing_without_a_span_log(window, reader,
                                                 monkeypatch):
    """A program without ``repro.runtime.tracing`` (the commit before
    it) reads as nothing, not as an error."""
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert _reader(reader).compute(window, None) is None


def _log(monkeypatch, *recs):
    """The span log reads ``recs``, as (name, duration s, counts)."""
    from repro.runtime.tracing import Record
    log = [Record(i, n, float(i), i + d, None, c)
           for i, (n, d, c) in enumerate(recs)]
    monkeypatch.setattr(spans, "records", lambda rec: log)


def test_page_fill_leaves_out_a_horizon_that_failed_to_plan(monkeypatch):
    """A horizon whose planning raised (a full window) closes its span
    without grid counts, and the server serves on."""
    grid = {"pages": 3, "bucket_rows": 2, "table_width": 4}
    _log(monkeypatch, ("server.horizon", 0.1, grid),
         ("server.horizon", 0.1, {}))
    assert _reader("attn_page_fill").compute({}, None) == 37.5


def test_prefill_rate_needs_prompts_of_one_chunk(monkeypatch):
    """A chunk that does not complete its prompt leaves its program's
    time to a later wait: the window reads None, not a rate."""
    final = ("server.prefill", 0.5, {"tokens": 1000, "final": True})
    _log(monkeypatch, final)
    assert _reader("prefill_ms_per_ktok").compute({}, None) == 500
    _log(monkeypatch, ("server.prefill", 0.01,
                       {"tokens": 1024, "final": False}), final)
    assert _reader("prefill_ms_per_ktok").compute({}, None) is None


def test_gap_lands_on_the_innermost_program_span():
    """One horizon program [6, 18) ms between others; the host plans
    it inside the driver's ``server.horizon_batch`` and commits after
    the wait: each idle gap goes to the program phase under it."""
    dev = _plane(1, "/device:TPU:0",
                 _line(1, "XLA Modules", [(1, 0, 2), (1, 6, 18),
                                          (1, 35, 40)]) +
                 _line(2, "XLA Ops", [(2, 0, 2), (2, 6, 18), (2, 35, 40)]),
                 {1: "jit_decode_horizon_step(11)", 2: "fusion.1"})
    names = {1: "scheduler.step", 2: "scheduler.iteration",
             3: "server.horizon_batch", 4: "server.horizon",
             5: "server.horizon.plan", 6: "server.horizon.dispatch",
             7: "server.horizon.wait", 8: "server.horizon.commit"}
    host = _plane(2, "/host:CPU",
                  _line(1, "python", [(1, 1, 34), (2, 1, 34), (3, 2, 33),
                                      (4, 2, 33), (5, 2, 5), (6, 5, 6),
                                      (7, 6, 18), (8, 18, 33)]),
                  names)
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(dev + host))
    tr = trace.reduce_data(pd, 0.0, 40 * MS / 1e12)
    assert dict(tr["breakdown"]["idle_gaps"]) == pytest.approx(
        {"server.horizon.plan": 0.004, "server.horizon.commit": 0.017})
