"""What every model's plain reference shares: float32 matmuls at `highest`
precision with the lower-precision control, RMSNorm and rotary embedding,
and the padding of one teacher-forced sequence.

A model module (`models/<name>.py`) writes its forward pass from these,
importing nothing of the program.  With `low="fp8"` every matmul with a
weight takes its inputs in fp8 instead (weights scaled per output channel,
activations per token, symmetric; float8_e4m3fn values, float32
accumulation): the lower-precision control.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# largest magnitude each low precision holds
_LOW_MAX = {"fp8": 448.0}


def check_low(low) -> None:
    if low is not None and low not in _LOW_MAX:
        raise ValueError(f"unknown low precision {low!r}")


def _round_low(x: jax.Array, axis: int, low: str) -> jax.Array:
    """`x` rounded to `low` with one symmetric scale along `axis`, returned
    in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _LOW_MAX[low]
    scale = jnp.where(scale == 0, 1.0, scale)
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * scale


def matmul(x: jax.Array, w: jax.Array, low) -> jax.Array:
    """x [T, in] float32 @ w [in, out] -> float32, the inputs first rounded
    to `low` where it is set."""
    w = w.astype(jnp.float32)
    if low is not None:
        x, w = _round_low(x, 1, low), _round_low(w, 0, low)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def rope(x, positions, theta):
    """x [T, heads, hd]: rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def padded_length(n: int) -> int:
    """Sequences are padded to a power of two of at least 512 tokens, so a
    run compiles at most a handful of reference programs."""
    return max(512, 1 << (n - 1).bit_length())


def teacher_forced(forward: Callable, params, prompt: Sequence[int],
                   served: Sequence[int]) -> np.ndarray:
    """Logits [len(served), V] (float32) that predict each served token from
    the prompt and the served tokens before it.  `forward(params, tokens,
    out_pos)` is a model's jitted reference over one padded sequence,
    returning the logits at the positions `out_pos`."""
    seq = list(prompt) + list(served[:-1])
    T = padded_length(len(seq))
    tokens = np.zeros(T, np.int32)
    tokens[: len(seq)] = seq
    first = len(prompt) - 1
    out_pos = np.full(padded_length(len(served)), first, np.int32)
    out_pos[: len(served)] = first + np.arange(len(served))
    out = forward(params, jnp.asarray(tokens), jnp.asarray(out_pos))
    return np.asarray(out[: len(served)], np.float32)
