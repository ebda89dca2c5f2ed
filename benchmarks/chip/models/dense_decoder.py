"""Dense decoder (Qwen1.5 / InternLM2 family): its weights from the seed, its
plain reference and its operation counts, for every configuration whose file
names `"model": "dense_decoder"`.

The architecture, written from the published description and independent of
the program: token embedding; per layer RMSNorm, q/k/v projections (with
biases where the configuration has them), rotary embedding on the two halves
of each head, causal grouped-query attention (query head h reads key/value
head h // (H / KH)), output projection, RMSNorm and a SwiGLU MLP, each with a
residual; a final RMSNorm and the output head.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights
from counts import DTYPE_BYTES

# Canonical per-layer names and the program's name for each leaf of its
# one stacked decoder block (leaves `[stages, layers per stage, ...]`).
PROGRAM_LAYER_NAMES = {
    "attn_norm": "ln1_g", "mlp_norm": "ln2_g",
    "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
    "bq": "bq", "bk": "bk", "bv": "bv",
    "w_gate": "w_gate", "w_up": "w_up", "w_down": "w_down",
}

# the configuration keys the reference reads
REFERENCE_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "vocab_size", "rms_norm_eps", "rope_theta")


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes a configuration file states, under short names."""
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "KH": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "V": cfg["vocab_size"]}


# ----------------------------------------------------------------- program


def check_program(cfg: Dict, server) -> None:
    """Raise unless the program serves the model the configuration file
    states."""
    pc, engine = server.cfg, server.replicas[0]
    stacked = pc.layers_per_stage * pc.plan.pp
    want = {"hidden_size": pc.d_model, "intermediate_size": pc.d_ff,
            "num_hidden_layers": stacked if pc.num_layers == stacked
            else f"{pc.num_layers} ({stacked} stacked)",
            "num_attention_heads": pc.num_heads,
            "num_key_value_heads": pc.num_kv_heads, "head_dim": pc.head_dim,
            "vocab_size": pc.vocab_size, "rope_theta": pc.rope_theta,
            "rms_norm_eps": pc.norm_eps, "qkv_bias": pc.qkv_bias,
            "torch_dtype": pc.dtype, "hidden_act": pc.act,
            "max_position_embeddings": engine.scheduler.max_model_len}
    bad = {k: (cfg[k], v) for k, v in want.items() if cfg[k] != v}
    if pc.norm != "rmsnorm" or pc.padded_vocab != pc.vocab_size:
        bad["norm / padded vocab"] = ("rmsnorm / vocab",
                                      f"{pc.norm} / {pc.padded_vocab}")
    if bad:
        raise ValueError(f"the program's {pc.name} differs from "
                         f"{cfg['name']}'s file (file, program): {bad}")


# ----------------------------------------------------------------- weights


def layer_shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    """Canonical per-layer leaf shapes, without the leading layer axis.
    Matrices are `[in, out]`."""
    n = dims(cfg)
    d, ff, q, kv = n["d"], n["ff"], n["H"] * n["hd"], n["KH"] * n["hd"]
    shapes = {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
              "wo": (q, d), "mlp_norm": (d,), "w_gate": (d, ff),
              "w_up": (d, ff), "w_down": (ff, d)}
    if cfg["qkv_bias"]:
        shapes.update(bq=(q,), bk=(kv,), bv=(kv,))
    return shapes


def make_canonical(cfg: Dict, key: jax.Array) -> Dict[str, Any]:
    """Weights of the whole model from `key`, in `cfg["torch_dtype"]`:
    matrices and biases normal with the configuration's initializer range
    (output projections scaled by 1/sqrt(2L)), norm gains around 1, and the
    output head the embedding's transpose where the embeddings are tied.
    Traceable: call it inside one `jax.jit`."""
    n = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg["initializer_range"])
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 3)

    def draw(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        full = (n["L"],) + shape
        if name.endswith("norm"):
            layers[name] = (1.0 + draw(k, full, 0.1)).astype(dtype)
        elif name in ("wo", "w_down"):
            layers[name] = draw(k, full, std / math.sqrt(2 * n["L"]))
        else:
            layers[name] = draw(k, full, std)
    embed = draw(keys[-3], (n["V"], n["d"]), std)
    head = (embed.T if cfg["tie_word_embeddings"]
            else draw(keys[-2], (n["d"], n["V"]), std))
    final_norm = (1.0 + draw(keys[-1], (n["d"],), 0.1)).astype(dtype)
    return {"embed": embed, "head": head, "final_norm": final_norm,
            "layers": layers}


def _block_key(program_like: Dict) -> str:
    blocks = list(program_like["stages"])
    if len(blocks) != 1:
        raise ValueError(f"the program's stage holds blocks {blocks}; this "
                         "map knows one decoder block kind")
    return blocks[0]


def to_program(canonical: Dict, program_like: Dict) -> Dict:
    """The program's parameter tree holding `canonical`'s values.
    `program_like` is the program's own tree (arrays or shape structs); its
    stacked leaves `[S, R, ...]` take the layer axis split as `L = S * R`."""
    block = _block_key(program_like)
    like_layers = program_like["stages"][block]
    layers = {}
    for name, arr in canonical["layers"].items():
        target = like_layers[PROGRAM_LAYER_NAMES[name]]
        layers[PROGRAM_LAYER_NAMES[name]] = arr.reshape(target.shape)
    tree = {"embed": {"tok": canonical["embed"]},
            "stages": {block: layers},
            "final_norm": {"g": canonical["final_norm"]},
            "lm_head": {"w": canonical["head"]}}
    weights.check_same_layout(tree, program_like)
    return tree


def from_program(tree: Dict) -> Dict:
    """Inverse of `to_program`: the canonical view of a program-layout tree
    (reshapes only; traceable)."""
    block = _block_key(tree)
    names = {v: k for k, v in PROGRAM_LAYER_NAMES.items()}
    layers = {}
    for pname, arr in tree["stages"][block].items():
        layers[names[pname]] = arr.reshape((-1,) + arr.shape[2:])
    return {"embed": tree["embed"]["tok"], "head": tree["lm_head"]["w"],
            "final_norm": tree["final_norm"]["g"], "layers": layers}


def make_program_params(cfg: Dict, seed: int, program_like: Dict) -> Dict:
    """The seed's weights in the program's layout, placed as
    `program_like`'s leaves are."""
    return weights.place(
        lambda key, like: to_program(make_canonical(cfg, key), like),
        seed, program_like)


# --------------------------------------------------------------- reference


def _forward(cfg: Dict, low, w: Dict, tokens: jax.Array,
             out_pos: jax.Array) -> jax.Array:
    n = dims(cfg)
    H, KH, hd = n["H"], n["KH"], n["hd"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = tokens.shape[0]
    positions = jnp.arange(T)
    causal = positions[None, :] <= positions[:, None]
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    matmul, rmsnorm, rope = reference.matmul, reference.rmsnorm, reference.rope

    def layer(x, lw):
        h = rmsnorm(x, lw["attn_norm"], eps)
        q, k, v = (matmul(h, lw[m], low) for m in ("wq", "wk", "wv"))
        if "bq" in lw:
            q, k, v = (a + lw[b].astype(jnp.float32)
                       for a, b in ((q, "bq"), (k, "bk"), (v, "bv")))
        q = rope(q.reshape(T, H, hd), positions, theta)
        k = rope(k.reshape(T, KH, hd), positions, theta)
        v = v.reshape(T, KH, hd)
        k, v = jnp.repeat(k, H // KH, axis=1), jnp.repeat(v, H // KH, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + matmul(o.reshape(T, H * hd), lw["wo"], low)
        h = rmsnorm(x, lw["mlp_norm"], eps)
        up = jax.nn.silu(matmul(h, lw["w_gate"], low)) * matmul(
            h, lw["w_up"], low)
        return x + matmul(up, lw["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    h = rmsnorm(jnp.take(x, out_pos, axis=0), w["final_norm"], eps)
    return matmul(h, w["head"], low)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: tuple, low):
    cfg = dict(cfg_items)

    def fn(tree, tokens, out_pos):
        return _forward(cfg, low, from_program(tree), tokens, out_pos)
    return jax.jit(fn)


def teacher_forced_logits(cfg: Dict, params: Dict,
                          prompt: Sequence[int], served: Sequence[int], *,
                          low=None) -> np.ndarray:
    """Logits [len(served), V] (float32, `highest`) that predict each served
    token from the prompt and the served tokens before it; with `low` every
    matmul with a weight takes its inputs at that precision (the control).
    `params` is the tree `make_program_params` made."""
    reference.check_low(low)
    fn = _compiled(tuple((k, cfg[k]) for k in REFERENCE_KEYS), low)
    return reference.teacher_forced(fn, params, prompt, served)


# ------------------------------------------------------------------ counts


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
