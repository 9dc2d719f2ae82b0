"""Operation and byte counts of the kernel files on hand-checked shapes."""
import os

from bench import harness

K = os.path.join(harness.BENCH, "kernels")
pa = harness.load_module(os.path.join(K, "paged_attention.py"))
ds = harness.load_module(os.path.join(K, "decode_step.py"))

GRANITE = {"num_hidden_layers": 40, "hidden_size": 2048,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 64, "intermediate_size": 8192, "vocab_size": 49155}
DIMS = dict(n_heads=32, n_kv_heads=8, head_dim=64)


def test_paged_attention_counts_valid_tokens_only():
    # two rows of 100 and 30 tokens, two padding rows
    ops, nbytes = pa.cost([100, 30, 0, 0], **DIMS)
    assert ops == 4 * 130 * 32 * 64 == 1064960
    # K and V: 130 tokens x 8 heads x 64 x 2 B x 2; q and o: 2 rows
    assert nbytes == 130 * 8 * 64 * 2 * 2 + 2 * 32 * 64 * 2 * 2 == 282624


def test_paged_attention_horizon():
    # one row at length 10 that emits 3 of a horizon of 4, two layers:
    # steps attend over 11, 12, 13 tokens
    ops, nbytes = pa.horizon_cost([(10, 3)], 4, 2, **DIMS)
    assert ops == 2 * 4 * 36 * 32 * 64
    assert nbytes == 2 * (36 * 8 * 64 * 4 + 3 * 32 * 64 * 4)


def test_decode_step_model_operations():
    per_layer = 2048 * (32 + 16) * 64 + 32 * 64 * 2048 + 3 * 2048 * 8192
    assert per_layer == 60817408
    assert ds.matmul_params(GRANITE) == 40 * per_layer + 2048 * 49155
    # one token at context 1000: matmuls plus attention
    want = 2 * (40 * per_layer + 2048 * 49155) + 4 * 1000 * 32 * 64 * 40
    assert ds.token_ops(GRANITE, 1000) == want
    assert ds.horizon_ops(GRANITE, [(999, 1), (5, 0)], 8) == want
