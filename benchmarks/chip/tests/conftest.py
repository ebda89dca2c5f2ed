"""Shared set-up of the benchmark's CPU tests: the benchmark's directory on
the import path, and a tiny cell that runs the harness end to end on the
CPU through the program's chip-mode build."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DIMS = {"pages": 256, "page": 8, "C": 32, "Sp": 1, "Sd": 8, "Bp": 32,
             "Bd": 32, "slots": 64}

TINY_CONFIG = {
    "name": "tiny",
    "engine": {"arch": "tiny", "reduced": False, "stages": 1,
               "policy": "gllm", "dispatch": "async", "bucketed": False,
               "enable_prefix_caching": False, "dims": TINY_DIMS},
    "hidden_size": 512, "intermediate_size": 1024, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
    "vocab_size": 4096, "max_position_embeddings": 256,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "hidden_act": "silu", "qkv_bias": True,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "torch_dtype": "bfloat16",
    # at this size, on the CPU, over four seeds: bf16 serving read logit
    # gaps of 0 to 7.8e-3 and log-prob errors of 8.6e-3 to 1.0e-2 against
    # the float32 reference; the fp8 control 8.0e-2 to 0.15 and 0.11 to
    # 0.18 on the same requests
    "check": {"logit_gap_limit": 0.015, "logprob_error_limit": 0.018},
}

TINY_TRAFFIC = {
    "name": "tiny-chat", "generator": "lognormal_poisson",
    "rate_per_s": 20.0, "lead_in_s": 0.5,
    "prompt": {"mean": 24, "sigma": 0.6, "min": 4, "max": 120},
    "output": {"mean": 16, "sigma": 0.6, "min": 1, "max": 40},
    "temperature": 0.0,
}


def tiny_arch():
    from repro.configs import ArchConfig, BlockKind, BlockSpec, ParallelPlan
    return ArchConfig(
        name="tiny", family="dense", num_layers=2, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=1024, vocab_size=4096,
        pattern=(BlockSpec(BlockKind.ATTN_MLP, 2),),
        plan=ParallelPlan(pp=1, tp=1), qkv_bias=True, rope_theta=1e4)


@pytest.fixture
def tiny_cell(monkeypatch):
    """A one-chip cell of the tiny model, with the program's registry
    answering the tiny architecture; the end-to-end metrics of the chat
    cells."""
    import repro.configs

    import manifest
    monkeypatch.setattr(repro.configs, "get_config", lambda name: tiny_arch())
    e2e = [manifest.Metric(n, u) for n, u in (
        ("ttft_p50_ms", "ms"), ("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
        ("output_tokens_per_s", "tokens/s"), ("setup_s", "s"))]
    return manifest.Cell("tiny.chat", 1, dict(TINY_CONFIG),
                         dict(TINY_TRAFFIC), e2e, [])
