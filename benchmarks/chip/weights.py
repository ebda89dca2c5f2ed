"""Random weights from the seed, made by the benchmark and placed as the
program's own parameters are: what every model module shares.

A model module (`models/<name>.py`) draws its weights from the key made
here, in its own layout, and maps them into the program's parameter tree.
The benchmark hands these arrays to the server it measures and keeps them for
the plain reference, which so takes nothing that the program made.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative whole number (more than 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


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


def place(make: Callable[[jax.Array, Dict], Dict], seed: int,
          program_like: Dict) -> Dict:
    """One jitted call: `make(key, shapes)`, the tree in the program's
    layout drawn from the seed's key, placed as `program_like`'s leaves are
    (same shardings).  `shapes` is `program_like` as shape structs."""
    shardings = jax.tree.map(lambda a: a.sharding, program_like)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          program_like)
    fn = jax.jit(lambda k: make(k, shapes), out_shardings=shardings)
    return fn(seed_key(seed))
