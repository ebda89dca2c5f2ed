"""Operation and byte counts, against a hand count at one small shape."""

from types import SimpleNamespace

from counts import attention_cost, layer_matmul_params, step_flops
from serve_loop import BatchCount, count_batch

SMALL = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "vocab_size": 10, "torch_dtype": "bfloat16"}


def _seq(start, n, produces=True):
    return SimpleNamespace(start_pos=start, num_tokens=n,
                           produces_token=produces)


def test_count_batch_by_hand():
    pre = _seq(3, 2)                    # keys 4 + 5 = 9, reads 5
    dec = _seq(4, 1)                    # keys 5, reads 5
    batch = SimpleNamespace(prefill=[pre], decode=[dec], seqs=[pre, dec],
                            num_prefill_tokens=2, num_decode_tokens=1)
    assert count_batch(batch) == BatchCount(2, 1, 2, 14, 10)


def test_step_and_kernel_counts_by_hand():
    # per layer: q 4x4, k and v 4x2 each, o 4x4, gate/up/down 4x8 each
    assert layer_matmul_params(SMALL) == 16 + 8 + 8 + 16 + 96
    b = BatchCount(prefill_tokens=2, decode_tokens=1, sampled_rows=2,
                   attended_keys=14, context_tokens=10)
    matmuls = 2 * 2 * 144 * 3           # 2 flops x 2 layers x 144 x tokens
    attention = 4 * 2 * 2 * 2 * 14      # 4 x layers x H x hd x keys
    head = 2 * 4 * 10 * 2               # 2 x d x V x sampled rows
    assert step_flops(SMALL, [b]) == matmuls + attention + head == 2336
    cost = attention_cost(SMALL, [b])
    assert cost["flops"] == attention == 448
    # bf16: layers x 2 B x (2 x KH x hd x 10 context + 2 x H x hd x 3 rows)
    assert cost["bytes"] == 2 * 2 * (2 * 1 * 2 * 10 + 2 * 2 * 2 * 3) == 256
