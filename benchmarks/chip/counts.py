"""Operations and bytes the algorithm needs, from a configuration's shapes
and the work scheduled (`serve_loop.BatchCount`), never from the compiler.

A multiply-add counts as 2 operations.  Bytes are those the attention kernel
has to move at the least: each sequence's keys and values once, and its
query rows in and out, in the served dtype.
"""

from __future__ import annotations

from typing import Dict, Iterable

from weights import dims

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies in one decoder layer."""
    n = dims(cfg)
    q, kv = n["H"] * n["hd"], n["KH"] * n["hd"]
    return n["d"] * q + 2 * n["d"] * kv + q * n["d"] + 3 * n["d"] * n["ff"]


def step_flops(cfg: Dict, batches: Iterable) -> float:
    """Model operations of the scheduled (unpadded) work: the layers'
    matmuls for every token, attention over the causal keys, and the output
    head for every row that samples a token."""
    n = dims(cfg)
    per_token = 2 * n["L"] * layer_matmul_params(cfg)
    per_key = 4 * n["L"] * n["H"] * n["hd"]        # q.k and p.v
    head = 2 * n["d"] * n["V"]
    return float(sum(per_token * (b.prefill_tokens + b.decode_tokens)
                     + per_key * b.attended_keys + head * b.sampled_rows
                     for b in batches))


def attention_cost(cfg: Dict, batches: Iterable) -> Dict[str, float]:
    """Operations and bytes of the paged-attention kernel over all layers:
    4*H*hd per query-key pair; each sequence's keys and values read once
    (2*KH*hd per context token) and each query row read and written
    (2*H*hd per query token)."""
    n = dims(cfg)
    b_el = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = bytes_ = 0
    for b in batches:
        q_tokens = b.prefill_tokens + b.decode_tokens
        flops += 4 * n["H"] * n["hd"] * b.attended_keys
        bytes_ += b_el * (2 * n["KH"] * n["hd"] * b.context_tokens
                          + 2 * n["H"] * n["hd"] * q_tokens)
    return {"flops": float(n["L"] * flops), "bytes": float(n["L"] * bytes_)}
