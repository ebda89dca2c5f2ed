"""Plain reference of a dense decoder (Qwen1.5 / InternLM2 family), written
from the published description and independent of the program: token
embedding; per layer RMSNorm, q/k/v projections (with biases where the
configuration has them), rotary embedding on the two halves of each head,
causal grouped-query attention (query head h reads key/value head
h // (H / KH)), output projection, RMSNorm and a SwiGLU MLP, each with a
residual; a final RMSNorm and the output head.

`teacher_forced_logits` runs it over one sequence at float32 and `highest`
matmul precision.  With `low="fp8"` every matmul with a weight takes its
inputs in fp8 instead (weights scaled per output channel, activations per
token, symmetric; float8_e4m3fn values, float32 accumulation): the
lower-precision control.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import dims, from_program


# largest magnitude each low precision holds
_LOW_MAX = {"fp8": 448.0}


def _round_low(x: jax.Array, axis: int, low: str) -> jax.Array:
    """`x` rounded to `low` with one symmetric scale along `axis`, returned
    in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _LOW_MAX[low]
    scale = jnp.where(scale == 0, 1.0, scale)
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * scale


def _matmul(x: jax.Array, w: jax.Array, low) -> jax.Array:
    """x [T, in] float32 @ w [in, out] -> float32, the inputs first rounded
    to `low` where it is set."""
    w = w.astype(jnp.float32)
    if low is not None:
        x, w = _round_low(x, 1, low), _round_low(w, 0, low)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(cfg: Dict, low, w: Dict, tokens: jax.Array,
             out_pos: jax.Array) -> jax.Array:
    n = dims(cfg)
    H, KH, hd = n["H"], n["KH"], n["hd"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = tokens.shape[0]
    positions = jnp.arange(T)
    causal = positions[None, :] <= positions[:, None]
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def layer(x, lw):
        h = _rmsnorm(x, lw["attn_norm"], eps)
        q, k, v = (_matmul(h, lw[m], low) for m in ("wq", "wk", "wv"))
        if "bq" in lw:
            q, k, v = (a + lw[b].astype(jnp.float32)
                       for a, b in ((q, "bq"), (k, "bk"), (v, "bv")))
        q = _rope(q.reshape(T, H, hd), positions, theta)
        k = _rope(k.reshape(T, KH, hd), positions, theta)
        v = v.reshape(T, KH, hd)
        k, v = jnp.repeat(k, H // KH, axis=1), jnp.repeat(v, H // KH, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _matmul(o.reshape(T, H * hd), lw["wo"], low)
        h = _rmsnorm(x, lw["mlp_norm"], eps)
        up = jax.nn.silu(_matmul(h, lw["w_gate"], low)) * _matmul(
            h, lw["w_up"], low)
        return x + _matmul(up, lw["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    h = _rmsnorm(jnp.take(x, out_pos, axis=0), w["final_norm"], eps)
    return _matmul(h, w["head"], low)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: tuple, low):
    cfg = dict(cfg_items)

    def fn(tree, tokens, out_pos):
        return _forward(cfg, low, from_program(tree), tokens, out_pos)
    return jax.jit(fn)


def padded_length(n: int) -> int:
    """Sequences are padded to a power of two of at least 512 tokens, so a
    run compiles at most a handful of reference programs."""
    return max(512, 1 << (n - 1).bit_length())


def teacher_forced_logits(cfg: Dict, params: Dict,
                          prompt: Sequence[int], served: Sequence[int], *,
                          low=None) -> np.ndarray:
    """Logits [len(served), V] (float32) that predict each served token from
    the prompt and the served tokens before it.  `params` is the tree the
    benchmark made in the program's layout (`weights.make_program_params`)."""
    seq = list(prompt) + list(served[:-1])
    T = padded_length(len(seq))
    tokens = np.zeros(T, np.int32)
    tokens[: len(seq)] = seq
    first = len(prompt) - 1
    out_pos = np.full(padded_length(len(served)), first, np.int32)
    out_pos[: len(served)] = first + np.arange(len(served))
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rms_norm_eps", "rope_theta")
    if low is not None and low not in _LOW_MAX:
        raise ValueError(f"unknown low precision {low!r}")
    fn = _compiled(tuple((k, cfg[k]) for k in keys), low)
    out = fn(params, jnp.asarray(tokens), jnp.asarray(out_pos))
    return np.asarray(out[: len(served)], np.float32)
