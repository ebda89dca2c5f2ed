"""Dense (non-pipelined, non-paged) reference forward + greedy generation.

The oracle the serving engine is validated against: identical parameters,
identical stage-ordered layer application (including the whisper staircase
and the padded-layer mask), but executed as one dense forward over the full
sequence — no pipeline, no paged KV, no chunking.  Used by the equivalence
tests and the Table-1-style output-quality benchmark.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.models.transformer import _block_key


def dense_forward(cfg: ArchConfig, params, tokens: jax.Array,
                  enc_embeds: Optional[jax.Array] = None,
                  enc_width: int = 0) -> jax.Array:
    """tokens [B, T] -> logits [B, T(+Te), V].  For enc-dec, `enc_embeds`
    [B, Te, d] is prepended as the encoder stream (tokens are the decoder
    side); returned logits cover the concatenated payload — slice the
    decoder half for next-token prediction."""
    h = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if enc_embeds is not None:
        h = jnp.concatenate([enc_embeds.astype(h.dtype), h], axis=1)
        enc_width = enc_embeds.shape[1]
    aux = jnp.zeros((), jnp.float32)
    Lps = cfg.layers_per_stage
    for s in range(cfg.plan.pp):
        off = 0
        for i, bs in enumerate(cfg.pattern):
            p = params["stages"][_block_key(i, bs)]
            for r in range(bs.repeat):
                g = s * Lps + off + r
                if g < cfg.num_layers:
                    pl = jax.tree.map(lambda a: a[s, r], p)
                    h, aux = tfm.block_apply_train(
                        cfg, bs.kind, pl, h, aux, enc_width=enc_width)
            off += bs.repeat
    return tfm.head_apply(cfg, params, h)


def greedy_generate(
    cfg: ArchConfig,
    params,
    prompt: Sequence[int],
    max_new_tokens: int,
    enc_embeds: Optional[np.ndarray] = None,
) -> List[int]:
    """Greedy decoding by full recompute each step (slow, exact)."""
    toks = list(prompt)
    out: List[int] = []
    enc = None if enc_embeds is None else jnp.asarray(enc_embeds)[None]
    for _ in range(max_new_tokens):
        logits = dense_forward(cfg, params, jnp.asarray([toks], jnp.int32),
                               enc_embeds=enc)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def greedy_generate_logits(
    cfg: ArchConfig,
    params,
    prompt: Sequence[int],
    max_new_tokens: int,
    *,
    width: int,
) -> Tuple[List[int], np.ndarray]:
    """`greedy_generate` that also returns each step's next-token logits
    [max_new_tokens, V] (float32), for causal decoder-only models.

    The forward is jitted once at a fixed `width` >= len(prompt) +
    max_new_tokens: the tokens sit at the front of a zero-padded row, and
    under causal attention the padding after them changes no logit at
    their positions, so every step reuses one compiled program."""
    if len(prompt) + max_new_tokens > width:
        raise ValueError(f"width {width} < prompt {len(prompt)} + "
                         f"{max_new_tokens} new tokens")
    fwd = jax.jit(lambda p, t: dense_forward(cfg, p, t)[0])
    row = np.zeros((1, width), np.int32)
    row[0, :len(prompt)] = prompt
    n = len(prompt)
    out: List[int] = []
    logits = []
    for _ in range(max_new_tokens):
        step = np.asarray(fwd(params, jnp.asarray(row))[n - 1], np.float32)
        nxt = int(np.argmax(step))
        logits.append(step)
        out.append(nxt)
        row[0, n] = nxt
        n += 1
    return out, np.stack(logits)
