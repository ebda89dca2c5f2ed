"""Paged flash attention — the serving hot spot, TPU-native.

One kernel serves both phases (the gLLM merged micro-batch):
  * decode:  q [S, 1, H, D]   — one new token against a 32k-page context
  * prefill: q [S, C, H, D]   — a throttled chunk, causal vs. its positions

TPU adaptation of the vLLM GPU kernel (DESIGN.md §6): the block-table
indirection moves into the BlockSpec index_map via scalar prefetch — the
grid walks (seq, q-block, page) and the KV BlockSpec *fetches page
`tables[s, b]` from HBM into VMEM* while the previous page is being
consumed (hardware double-buffering replaces the GPU's manual smem staging).
Online softmax state lives in VMEM scratch across the minor (page) grid dim.
The pool is stored lane-dense, [P, page, KH·2·D] with the lanes ordered
[KH, 2, D] (each head's K then V), so a page block (1, page, KH·2·D) is the
array's own row-major (8, 128) tiling and XLA has no layout to convert
between the stored pool and the kernel (DESIGN.md §6); head kh's K sits in
lanes [2·kh·D, (2·kh+1)·D) and its V in the next D.  The q and output
blocks take whole (H, D) trailing dims and q-blocks of 128 rows (or all of
TQ).  The query positions travel as [S, 1, TQ] so their block's trailing
dims are (1, tq).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    tables_ref,            # [S * B] int32 (flattened block tables)
    ctx_ref,               # [S] int32 context lens
    live_ref,              # [S] int32 live pages per sequence
    # inputs
    q_ref,                 # [1, TQ, H, D]
    qpos_ref,              # [1, 1, TQ] int32 global positions
    kv_ref,                # [1, page, KH·2·D] — page tables[s, b]
    # outputs
    o_ref,                 # [1, TQ, H, D]
    # scratch
    acc_ref,               # [TQ, H, D] f32
    m_ref,                 # [TQ, H] f32
    l_ref,                 # [TQ, H] f32
    *,
    kv_heads: int,
    page: int,
    num_pages: int,
):
    s, qb, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Dead-page skip: pages at or past live_ref[s] hold no in-context keys,
    # so their masked contribution is exactly zero (every score is NEG_INF,
    # which after the running-max subtraction underflows to p == 0.0 and
    # alpha == 1.0).  Skipping the whole update is therefore bit-identical
    # while saving the MXU work; the index_map already clamps the DMA to the
    # last live page so no extra HBM traffic happens either.
    @pl.when(b < live_ref[s])
    def _update():
        q = q_ref[0].astype(jnp.float32)                # [TQ, H, D]
        TQ, H, D = q.shape
        KH = kv_heads
        G = H // KH

        def k_of(kh):                                   # [page, D]
            return kv_ref[0, :, 2 * kh * D:(2 * kh + 1) * D].astype(jnp.float32)

        def v_of(kh):                                   # [page, D]
            return kv_ref[0, :, (2 * kh + 1) * D:(2 * kh + 2) * D].astype(
                jnp.float32)

        kpos = b * page + jax.lax.broadcasted_iota(jnp.int32, (page,), 0)
        ctx = ctx_ref[s]
        qpos = qpos_ref[0, 0]                           # [TQ]
        mask = (kpos[None, :] < ctx) & (kpos[None, :] <= qpos[:, None])

        scale = D ** -0.5
        parts = []
        for kh in range(KH):
            qg = q[:, kh * G:(kh + 1) * G, :].reshape(TQ * G, D)
            sc = jax.lax.dot_general(qg, k_of(kh),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            parts.append(sc.reshape(TQ, G, page))
        scores = jnp.concatenate(parts, axis=1) * scale  # [TQ, H, page]
        scores = jnp.where(mask[:, None, :], scores, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])          # [TQ, H, page]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_new

        pv_parts = []
        for kh in range(KH):
            pg = p[:, kh * G:(kh + 1) * G, :].reshape(TQ * G, page)
            pv = jax.lax.dot_general(pg, v_of(kh),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            pv_parts.append(pv.reshape(TQ, G, D))
        pv = jnp.concatenate(pv_parts, axis=1)          # [TQ, H, D]
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    @pl.when(b == num_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "q_block"))
def paged_flash_attention(
    q: jax.Array,            # [S, TQ, H, D]
    kv_pages: jax.Array,     # [P, page, KH·2·D], lanes [KH, 2, D]
    block_tables: jax.Array, # [S, B] int32
    context_lens: jax.Array, # [S] int32
    q_positions: jax.Array,  # [S, TQ] int32
    *,
    q_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    S, TQ, H, D = q.shape
    P, page, L = kv_pages.shape
    KH = L // (2 * D)
    B = block_tables.shape[1]
    tq = min(q_block, TQ)
    assert TQ % tq == 0, (TQ, tq)

    grid = (S, TQ // tq, B)

    # Pages >= ceil(ctx / page) hold no in-context keys; the kernel skips
    # them (bit-identically — see _kernel) and the index_map re-fetches the
    # last live page instead of streaming dead ones from HBM.
    live_pages = jnp.minimum(
        jax.lax.div(context_lens + (page - 1), page), B).astype(jnp.int32)

    def q_index(s, qb, b, tables, ctx, live):
        return (s, qb, 0, 0)

    def pos_index(s, qb, b, tables, ctx, live):
        return (s, 0, qb)

    def kv_index(s, qb, b, tables, ctx, live):
        bb = jnp.minimum(b, jnp.maximum(live[s] - 1, 0))
        return (tables[s * B + bb], 0, 0)

    kernel = functools.partial(_kernel, kv_heads=KH, page=page, num_pages=B)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq, H, D), q_index),
                pl.BlockSpec((1, 1, tq), pos_index),
                pl.BlockSpec((1, page, L), kv_index),
            ],
            out_specs=pl.BlockSpec((1, tq, H, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((tq, H, D), jnp.float32),
                pltpu.VMEM((tq, H), jnp.float32),
                pltpu.VMEM((tq, H), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, TQ, H, D), q.dtype),
        interpret=interpret,
    )(block_tables.reshape(-1), context_lens, live_pages, q,
      q_positions.reshape(S, 1, TQ), kv_pages)
    return out
