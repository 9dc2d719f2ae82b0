"""The serving cell's largest programs compile for a described v5e.

The KV window (``hbm_pages``) and the prefill chunk of the granite cell
are the largest the chip holds: the decode horizon at its largest batch
and widest page table must fit in HBM beside the weights, and the prefill
chunk's scalar-prefetched page table must fit in SMEM.  Compiled here,
for a chip that is described and not attached.
"""
import os

import pytest

from bench import harness

CELLS = ("granite-decode-batch",)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(cell, one_chip):
    import jax
    import jax.numpy as jnp
    from repro.models.api import get_model
    from repro.runtime.serve import PagedServer
    drv = harness.driver_module(cell)
    cfg, c = cell.config, cell.cell
    model = get_model(drv.arch_config(cfg), compute_dtype=jnp.bfloat16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: model.init(k, dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    srv = PagedServer(model, None, page_size=c["server"]["page_size"],
                      hbm_pages=4, dtype=jnp.bfloat16)
    srv._interpret = srv._jnp_attention = False       # the chip's path
    pages = (cfg["num_hidden_layers"], c["server"]["hbm_pages"],
             c["server"]["page_size"], cfg["num_key_value_heads"],
             cfg["head_dim"])
    state = {"k": sds(pages, jnp.bfloat16), "v": sds(pages, jnp.bfloat16)}
    shapes = drv.program_shapes(cell)
    b, w = max(shapes["horizon"])
    i32 = jnp.int32
    jax.jit(srv.decode_horizon_step, static_argnames=("horizon",),
            donate_argnums=(1,)).lower(
        params, state, sds((b, w), i32), sds((b,), i32), sds((b,), i32),
        sds((b,), i32), sds((), i32), sds((2,), jnp.uint32),
        sds((), jnp.float32), sds((), jnp.float32), sds((b,), i32),
        horizon=c["scheduler"]["horizon"]).compile()
    ch, rw = max(shapes["prefill"])
    jax.jit(srv.prefill_chunk_step, donate_argnums=(1,)).lower(
        params, state, sds((rw,), i32), sds((1, ch), i32), sds((), i32),
        sds((), i32)).compile()


@pytest.mark.parametrize("name", CELLS)
def test_largest_programs_compile(name, one_chip):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    _compile(harness.find_cell(name), one_chip)
