"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, so it can compile for a chip
that is described and not attached: these tests catch what interpret
mode cannot (block tiling, VMEM stores, unsupported primitives) at no
chip time.  Widths are the serving model's (granite-3-2b: 8 KV heads,
head_dim 64, 16-token pages) and the analytics store's (128-row pages
of 128 columns).  The whole decode-horizon program of each benchmark
cell is compiled at the cell's shapes, and its optimized HLO is held to
moving no copy of the KV store (nor, for the hybrid model, of its state
slots).  Nothing runs, so results are checked elsewhere.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import math
import os
import re

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


# the granite-decode-batch cell: batch 8, table width 256, horizon 8,
# 1,600 stacked pages of 16 tokens
CELL_B, CELL_PPS, CELL_H, CELL_PAGES = 8, 256, 8, 1600
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def test_decode_horizon_moves_no_store_copy(one_chip):
    """``decode_horizon_step`` at the cell's shapes: no step copies or
    slices the store — no rematerialisation, no dynamic-slice or
    dynamic-slice fusion whose result is one layer's pages or more, and
    no such ``copy`` but the relayout of each store array into the
    kernel's layout on entry and back on exit (the store rests in the
    device's default layout, which puts the pages axis minor).  The
    temporaries hold the store once, in the kernel's layout, beside
    less than 1 GB (12.8 GB before the store was carried through the
    layer loop, appended in place and read at a layer index)."""
    from repro.configs.base import get_arch
    from repro.models.api import get_model
    from repro.runtime.serve import PagedServer

    model = get_model(get_arch("granite_3_2b"), compute_dtype=jnp.bfloat16)
    cfg = model.cfg
    srv = PagedServer(model, None, page_size=PAGE, hbm_pages=2,
                      dtype=jnp.bfloat16)
    srv._jnp_attention = False
    # as the server jits it on the chip: interpreting nothing, donating
    # the store
    srv._interpret = False
    step = jax.jit(srv.decode_horizon_step, static_argnames=("horizon",),
                   donate_argnums=(1,))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: arg(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    store = (cfg.n_layers, CELL_PAGES, PAGE, cfg.n_kv_heads, cfg.hd)
    state = {n: arg(store, jnp.bfloat16) for n in ("k", "v")}
    row = arg((CELL_B,))
    compiled = step.lower(
        params, state, arg((CELL_B, CELL_PPS)), row, row, row, arg(()),
        arg((2,), jnp.uint32), arg((), jnp.float32), arg((), jnp.float32),
        row, horizon=CELL_H).compile()
    layer_slice = math.prod(store[1:])
    moved, relayouts, computation = [], 0, None
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace():
            computation = line.split()[0]
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.group(1), m.group(2), m.group(3)
        shape = tuple(int(x) for x in dims.split(",") if x)
        store_sized = (shape[-2:] == store[-2:] and
                       math.prod(shape) >= layer_slice)
        sliced = op == "dynamic-slice" or name.startswith("dynamic-slice")
        if (op == "copy" and shape == store and computation == "ENTRY"):
            relayouts += 1
        elif "remat" in name or (store_sized and (op == "copy" or sliced)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    assert relayouts <= 2 * len(state), relayouts
    # K and V in the kernel's layout: the 64-wide head dim padded to 128
    kernel_layout_store = math.prod(store[:-1]) * 128 * 2 * len(state)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kernel_layout_store + 2 ** 30, temp


# the granite-h-micro-reasoning32 cell: batch 32, table width 512,
# horizon 8, 8,192 pages of 16 tokens for the 4 attention layers, 32
# state slots (and the spare) for the 36 Mamba-2 layers
HYB_B, HYB_PPS, HYB_PAGES, HYB_SLOTS = 32, 512, 8192, 32


def test_hybrid_horizon_moves_no_state_copy(one_chip):
    """``decode_horizon_step`` of granite-4.0-h-micro at the cell's
    shapes: the state slots are read and written in place by the
    ``ssm_state_update`` kernel — no copy, dynamic slice or
    rematerialisation of a layer's slots or pages or more.  The only
    whole-array copies are the relayouts on entry and exit of the KV
    store (as in the dense program) and of the small conv tails."""
    from repro.configs.base import get_arch
    from repro.models.api import get_model
    from repro.runtime.serve import PagedServer

    model = get_model(get_arch("granite-4.0-h-micro"),
                      compute_dtype=jnp.bfloat16)
    cfg = model.cfg
    srv = PagedServer(model, None, page_size=PAGE, hbm_pages=2,
                      dtype=jnp.bfloat16, state_slots=1)
    srv._jnp_attention = False
    srv._interpret = False
    step = jax.jit(srv.decode_horizon_step, static_argnames=("horizon",),
                   donate_argnums=(1,))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: arg(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pages = (cfg.attn_layers, HYB_PAGES, PAGE, cfg.n_kv_heads, cfg.hd)
    ssm = (cfg.state_layers, HYB_SLOTS + 1, 64, 64, 128)
    conv = (cfg.state_layers, HYB_SLOTS + 1, 3 * 4352)
    state = {"k": arg(pages, jnp.bfloat16), "v": arg(pages, jnp.bfloat16),
             "ssm": arg(ssm, jnp.float32), "conv": arg(conv, jnp.float32),
             "rows": arg((HYB_SLOTS,))}
    row = arg((HYB_B,))
    compiled = step.lower(
        params, state, arg((HYB_B, HYB_PPS)), row, row, row, arg(()),
        arg((2,), jnp.uint32), arg((), jnp.float32), arg((), jnp.float32),
        row, horizon=CELL_H).compile()
    text = compiled.as_text()
    assert "ssm_state_update" in text
    moved, relayouts, computation = [], 0, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            computation = line.split()[0]
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.group(1), m.group(2), m.group(3)
        shape = tuple(int(x) for x in dims.split(",") if x)
        big = any(shape[-len(s) + 2:] == s[2:] and
                  math.prod(shape) >= math.prod(s[1:])
                  for s in (pages, ssm))
        sliced = op == "dynamic-slice" or name.startswith("dynamic-slice")
        if op == "copy" and shape in (pages, conv) and \
                computation == "ENTRY":
            relayouts += 1
        elif "remat" in name or (big and (op == "copy" or sliced)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    assert relayouts <= 6, relayouts
    kernel_layout_pages = math.prod(pages[:-1]) * 128 * 2 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kernel_layout_pages + 2 ** 31, temp
