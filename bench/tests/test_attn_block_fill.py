"""The ``attn_block_fill`` reader over a tiny serving window on the CPU
and over hand-made span logs, and the block rule its counts share with
the paged attention kernel (``pages_per_block``)."""
import math
import sys
import time

import pytest

from bench.tests.test_program_spans import _log, _reader, window  # noqa: F401

READER = "attn_block_fill"


def test_reader_gives_a_share_of_the_grid(window):  # noqa: F811
    got = _reader(READER).compute(window, None)
    assert got is not None and math.isfinite(got) and 0 < got <= 100, got


def test_reader_finds_nothing_in_an_empty_window(window):  # noqa: F811
    now = time.monotonic()
    assert _reader(READER).compute(dict(window, window=(now, now)),
                                   None) is None


def test_reader_finds_nothing_without_a_span_log(window,  # noqa: F811
                                                 monkeypatch):
    """A program without ``repro.runtime.tracing`` (the commit before
    it) reads as nothing, not as an error."""
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert _reader(READER).compute(window, None) is None


def test_block_fill_reads_blocks_over_the_block_grid(monkeypatch):
    """Σ ``blocks`` ÷ Σ ``block_grid`` over the horizons; a horizon that
    failed to plan, or one from a program whose kernel counts no blocks,
    is left out, and a window of only those reads None."""
    grid = {"pages": 3, "bucket_rows": 2, "table_width": 4}
    _log(monkeypatch, ("server.horizon", 0.1,
                       dict(grid, blocks=3, block_grid=4)),
         ("server.horizon", 0.1, dict(grid, blocks=1, block_grid=4)),
         ("server.horizon", 0.1, grid), ("server.horizon", 0.1, {}))
    assert _reader(READER).compute({}, None) == 50.0
    _log(monkeypatch, ("server.horizon", 0.1, grid))
    assert _reader(READER).compute({}, None) is None


@pytest.mark.parametrize("page", [4, 8, 16, 32, 64, 256])
def test_pages_per_block_fits_the_table_and_the_budget(page):
    """The block the kernel steps by, which the horizon's counts use: a
    power of two of pages, never wider than the table, and within the
    token budget wherever one page fits it."""
    from repro.kernels.paged_attention import BLOCK_TOKENS, pages_per_block
    for pps in range(1, 600):
        ppb = pages_per_block(pps, page)
        assert ppb & (ppb - 1) == 0 and 1 <= ppb <= pps
        assert ppb * page <= max(BLOCK_TOKENS, page)
        # a wider table never gets a narrower block
        assert ppb >= pages_per_block(max(pps - 1, 1), page)
