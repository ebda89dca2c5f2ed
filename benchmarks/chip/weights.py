"""Random weights from the seed, made by the benchmark, in the layout a
configuration file describes, and the map into the program's parameter tree.

The benchmark hands these arrays to the server it measures and keeps them for
the plain reference, which so takes nothing that the program made.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Canonical per-layer names and the program's name for each leaf of its
# one stacked decoder block (leaves `[stages, layers per stage, ...]`).
PROGRAM_LAYER_NAMES = {
    "attn_norm": "ln1_g", "mlp_norm": "ln2_g",
    "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
    "bq": "bq", "bk": "bk", "bv": "bv",
    "w_gate": "w_gate", "w_up": "w_up", "w_down": "w_down",
}


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes a configuration file states, under short names."""
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "KH": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "V": cfg["vocab_size"]}


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


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative whole number (more than 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


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
    check_same_layout(tree, program_like)
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


def check_same_layout(tree: Dict, like: Dict) -> None:
    """Raise unless `tree` has `like`'s structure, shapes and dtypes."""
    a, b = jax.tree.structure(tree), jax.tree.structure(like)
    if a != b:
        raise ValueError(f"weights do not fit the program's parameter tree:"
                         f"\n  made:    {a}\n  program: {b}")
    made = jax.tree_util.tree_flatten_with_path(tree)[0]
    for (path, x), y in zip(made, jax.tree.leaves(like)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(
                f"{jax.tree_util.keystr(path)}: made {x.shape} {x.dtype},"
                f" the program holds {y.shape} {y.dtype}")


def make_program_params(cfg: Dict, seed: int, program_like: Dict) -> Dict:
    """One jitted call: the seed's weights, placed as `program_like`'s
    leaves are (same shardings), in the program's layout."""
    shardings = jax.tree.map(lambda a: a.sharding, program_like)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          program_like)
    fn = jax.jit(lambda k: to_program(make_canonical(cfg, k), shapes),
                 out_shardings=shardings)
    return fn(seed_key(seed))
