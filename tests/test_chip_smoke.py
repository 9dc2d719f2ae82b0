"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's phases run here with interpret-mode kernels, so a change
that breaks the chip run's paths, arguments or checks fails in the test
suite instead of on the chip; ``main`` itself must refuse the CPU.
"""
import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import pytest

from repro.configs.base import get_arch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_cfg():
    return dataclasses.replace(get_arch("granite-3-2b").reduced(),
                               n_layers=2, vocab_size=64)


TINY_SERVE = dict(n_requests=2, prompt_len=9, gen=6, horizon=4, page_size=4,
                  hbm_pages=32)


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_main_refuses_cpu(smoke, capsys, chips):
    assert smoke.main(chips) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_serving_phase_matches_dense_reference(smoke):
    rec = smoke.serving_phase(_tiny_cfg(), dtype=jnp.bfloat16,
                              **TINY_SERVE)
    assert rec["tokens_served"] == 2 * 6
    assert rec["prefill_err"]["rel_rms"] <= smoke.LOGIT_REL_RMS_TOL
    assert rec["first_step_err"]["rel_rms"] <= smoke.LOGIT_REL_RMS_TOL
    dt = rec["decisive_tokens"]
    assert dt["checked"] > 0 and dt["agreed"] == dt["checked"]


def test_isp_phase_bit_identical(smoke):
    rec = smoke.isp_phase(n_pages=4, page_rows=16, n_cols=128, k=4)
    assert rec["filter_reduce_identical"] and rec["topk_identical"]
    assert rec["extent_bytes"] == 4 * 16 * 128 * 4
    assert len(rec["topk_ids"]) == 4


def test_pool_phase_one_node(smoke):
    rec = smoke.pool_phase(_tiny_cfg(), n_nodes=1, dtype=jnp.bfloat16,
                           **TINY_SERVE)
    assert rec["tokens_match"] and rec["prefill_max_abs"] == 0.0
    assert rec["placement"] == [0, 0]
