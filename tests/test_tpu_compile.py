"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, so it can compile for a chip
that is described and not attached: these tests catch what interpret
mode cannot (block tiling, VMEM stores, unsupported primitives) at no
chip time.  Widths are the serving model's (granite-3-2b: 8 KV heads,
head_dim 64, 16-token pages) and the analytics store's (128-row pages
of 128 columns).  Nothing runs, so results are checked elsewhere.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.isp_scan import scan_filter_reduce, topk_scan
from repro.kernels.paged_attention import paged_attention, paged_attention_q8

B, H, HKV, D, PAGE, N_PAGES, PPS = 8, 32, 8, 64, 16, 512, 32
ROWS, COLS, EXTENT_PAGES = 128, 128, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_compiles(one_chip, dtype):
    page = ((N_PAGES, PAGE, HKV, D), dtype)
    _compile(paged_attention, one_chip, ((B, H, D), dtype), page, page,
             ((B, PPS), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("code", ["int8", "float8_e4m3fn"])
def test_paged_attention_q8_compiles(one_chip, code):
    page = ((N_PAGES, PAGE, HKV, D), jnp.dtype(code))
    scale = ((N_PAGES, PAGE, HKV), jnp.float32)
    _compile(paged_attention_q8, one_chip, ((B, H, D), jnp.bfloat16),
             page, page, scale, scale, ((B, PPS), jnp.int32),
             ((B,), jnp.int32))


@pytest.mark.parametrize("code", ["float32", "int8"])
def test_scan_filter_reduce_compiles(one_chip, code):
    pool = ((EXTENT_PAGES, ROWS, COLS), jnp.dtype(code))
    table = ((EXTENT_PAGES,), jnp.int32)
    one = ((1,), jnp.int32)
    thresh = ((1,), jnp.float32)
    if code == "float32":
        _compile(lambda p, t, n, th: scan_filter_reduce(
            p, t, n, th, filter_col=3, filter_op="ge"),
            one_chip, pool, table, one, thresh)
    else:
        _compile(lambda p, s, t, n, th: scan_filter_reduce(
            p, t, n, th, scales=s, filter_col=3, filter_op="ge"),
            one_chip, pool, ((EXTENT_PAGES, ROWS), jnp.float32), table,
            one, thresh)


@pytest.mark.parametrize("code,metric", [("float32", "dot"),
                                         ("int8", "cosine")])
def test_topk_scan_compiles(one_chip, code, metric):
    pool = ((EXTENT_PAGES, ROWS, COLS), jnp.dtype(code))
    table = ((EXTENT_PAGES,), jnp.int32)
    one = ((1,), jnp.int32)
    query = ((1, COLS), jnp.float32)
    if code == "float32":
        _compile(lambda p, t, n, q: topk_scan(p, t, n, q, k=10,
                                              metric=metric),
                 one_chip, pool, table, one, query)
    else:
        _compile(lambda p, s, t, n, q: topk_scan(p, t, n, q, k=10,
                                                 metric=metric, scales=s),
                 one_chip, pool, ((EXTENT_PAGES, ROWS), jnp.float32),
                 table, one, query)
