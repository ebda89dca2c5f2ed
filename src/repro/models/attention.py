"""Attention paths: a shared flash (online-softmax) core consumed by
training (dense causal), chunked-prefill-over-pages, paged decode, and
sequence-sharded long-context decode (flash-decode merge over `data`).

The core iterates KV *blocks* through a provider callback so that paged
gathers and MLA latent expansion happen per-block inside the scan — the
[Tq, ctx] score matrix and the expanded MLA K/V never materialize in full.
The Pallas kernels in ``repro.kernels`` implement the same math with explicit
VMEM BlockSpecs; on this CPU container the jnp path is the execution path and
the kernels are validated in interpret mode (DESIGN.md §6).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _online_softmax_step(carry, blk, q, scale):
    """One flash block: q [*, Tq, H, D]; blk = (k, v, mask).

    k/v: [*, Bk, KH, D]; mask: [*, Tq, Bk] bool (True = attend), already
    broadcastable over heads.  Grouped heads (GQA): H = KH * G.
    """
    o, m, l = carry                      # o [*, Tq, H, Dv]; m,l [*, Tq, H]
    k, v, mask = blk
    H = q.shape[-2]
    KH = k.shape[-2]
    G = H // KH
    qg = q.reshape(q.shape[:-2] + (KH, G, q.shape[-1]))
    # operands stay in their storage dtype; the MXU accumulates in f32
    # (an explicit .astype(f32) on k/v lets XLA hoist a *whole-KV-pool*
    # f32 conversion out of the flash loop — §Perf iteration 1b)
    s = jnp.einsum("...qhgd,...khd->...qhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[..., :, None, None, :], s, NEG_INF)
    s = s.reshape(s.shape[:-4] + (s.shape[-4], H, s.shape[-1]))  # [*, Tq, H, Bk]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pg = p.reshape(p.shape[:-2] + (KH, G, p.shape[-1]))
    pv = jnp.einsum("...qhgk,...khd->...qhgd", pg.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    pv = pv.reshape(pv.shape[:-3] + (H, pv.shape[-1]))
    o_new = o * alpha[..., None] + pv
    return (o_new, m_new, l_new), None


def flash_attention_blocks(
    q: jax.Array,                                   # [*, Tq, H, D]
    kv_block_fn: Callable[[jax.Array], Tuple[jax.Array, jax.Array, jax.Array]],
    num_blocks: int,
    *,
    v_dim: Optional[int] = None,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax over `num_blocks` KV blocks from `kv_block_fn(i)`.

    Returns (out [*, Tq, H, Dv], m, l) — the un-normalized partials so callers
    can merge across shards (flash-decode); use `finalize_flash` for the
    normalized output.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    Dv = v_dim if v_dim is not None else q.shape[-1]
    shape = q.shape[:-1]
    o0 = jnp.zeros(shape + (Dv,), jnp.float32)
    m0 = jnp.full(shape, NEG_INF, jnp.float32)
    l0 = jnp.zeros(shape, jnp.float32)

    def body(carry, i):
        blk = kv_block_fn(i)
        return _online_softmax_step(carry, blk, q, scale)

    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), jnp.arange(num_blocks))
    return o, m, l


def finalize_flash(o: jax.Array, l: jax.Array, dtype) -> jax.Array:
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(dtype)


def merge_flash_partials(o, m, l, axis_name: str):
    """Flash-decode: combine per-shard (o, m, l) across `axis_name` — used for
    sequence-sharded KV in long-context decode (DESIGN.md §3)."""
    m_glob = jax.lax.pmax(m, axis_name)
    alpha = jnp.exp(m - m_glob)
    o = jax.lax.psum(o * alpha[..., None], axis_name)
    l = jax.lax.psum(l * alpha, axis_name)
    return o, m_glob, l


# ----------------------------------------------------------------------------
# Dense causal attention (training / smoke)
# ----------------------------------------------------------------------------

def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,      # [B, T, H|KH, D]
    *, block_k: int = 512, causal: bool = True,
) -> jax.Array:
    B, T = q.shape[0], q.shape[1]
    Bk = min(block_k, T)
    assert T % Bk == 0, (T, Bk)
    qpos = jnp.arange(T)

    def kv_blk(i):
        kb = jax.lax.dynamic_slice_in_dim(k, i * Bk, Bk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * Bk, Bk, axis=1)
        kpos = i * Bk + jnp.arange(Bk)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = jnp.ones((T, Bk), bool)
        return kb, vb, jnp.broadcast_to(mask, (B, T, Bk))

    o, m, l = flash_attention_blocks(q, kv_blk, T // Bk, v_dim=v.shape[-1])
    return finalize_flash(o, l, q.dtype)


def cross_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,      # q [B,Tq,H,D], kv [B,Tk,KH,D]
    k_valid: Optional[jax.Array] = None,           # [B, Tk] bool
    *, block_k: int = 512,
) -> jax.Array:
    B, Tq = q.shape[0], q.shape[1]
    Tk = k.shape[1]
    Bk = min(block_k, Tk)
    assert Tk % Bk == 0, (Tk, Bk)

    def kv_blk(i):
        kb = jax.lax.dynamic_slice_in_dim(k, i * Bk, Bk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * Bk, Bk, axis=1)
        if k_valid is None:
            mask = jnp.ones((B, Tq, Bk), bool)
        else:
            mb = jax.lax.dynamic_slice_in_dim(k_valid, i * Bk, Bk, axis=1)
            mask = jnp.broadcast_to(mb[:, None, :], (B, Tq, Bk))
        return kb, vb, mask

    o, m, l = flash_attention_blocks(q, kv_blk, Tk // Bk, v_dim=v.shape[-1])
    return finalize_flash(o, l, q.dtype)


# ----------------------------------------------------------------------------
# Paged attention (serving): query rows attend to block-table pages
# ----------------------------------------------------------------------------

def pack_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """k, v [..., KH, D] -> the paged pool's lane-dense rows [..., KH·2·D],
    head-major: head kh's K in lanes [2·kh·D, (2·kh+1)·D), its V in the
    next D (DESIGN.md §6)."""
    kv = jnp.stack([k, v], axis=-2)                   # [..., KH, 2, D]
    return kv.reshape(kv.shape[:-3] + (-1,))


def split_kv(rows: jax.Array, head_dim: int) -> Tuple[jax.Array, jax.Array]:
    """Inverse of `pack_kv`: [..., KH·2·D] -> k, v [..., KH, D]."""
    kv = rows.reshape(rows.shape[:-1] + (-1, 2, head_dim))
    return kv[..., 0, :], kv[..., 1, :]


def write_kv_pages(
    cache: jax.Array,                 # [Pages, page, KH·2·D] (MLA: [..., C])
    new_kv: jax.Array,                # [S, C, KH·2·D] / [S, C, Cdim]
    slot_pages: jax.Array,            # [S, C] int32 destination page per token
    slot_offsets: jax.Array,          # [S, C] int32 offset within page
    valid: jax.Array,                 # [S, C] bool (padding rows don't write)
) -> jax.Array:
    flat_kv = new_kv.reshape((-1,) + new_kv.shape[2:])
    # padded rows aim past the pool and are dropped (-1 would wrap to the
    # last page: jnp indexing normalizes negative indices before the drop)
    pages = jnp.where(valid, slot_pages, cache.shape[0]).reshape(-1)
    offs = slot_offsets.reshape(-1)
    return cache.at[pages, offs].set(flat_kv, mode="drop")


def _check_table_alignment(Bmax: int, pages_per_block: int) -> None:
    if pages_per_block <= 0 or Bmax % pages_per_block != 0:
        raise ValueError(
            f"block-table width {Bmax} is not a positive multiple of "
            f"pages_per_block={pages_per_block} (the flash gather "
            f"granularity, env knob REPRO_PAGES_PER_BLOCK): pick table "
            f"widths (ServeDims.Bp/Bd and any depth-bucket steps, "
            f"REPRO_DEPTH_STEPS) divisible by it, or change the knob")


def paged_attention(
    q: jax.Array,                     # [S, C, H, D] (C==1 for decode)
    cache: jax.Array,                 # [Pages, page, KH·2·D], head-major
    block_tables: jax.Array,          # [S, Bmax] int32
    context_lens: jax.Array,          # [S] int32 (incl. this step's tokens)
    q_positions: jax.Array,           # [S, C] int32 global positions
    *,
    pages_per_block: int = 8,
    merge_axis: Optional[str] = None, # flash-decode merge over this mesh axis
    shard_info: Optional[Tuple[jax.Array, int]] = None,  # (shard_idx, n_shards)
) -> jax.Array:
    """Chunked-prefill & decode attention over the paged KV pool.

    With `merge_axis`, block tables index a *local* pool shard holding an
    interleaved slice of the sequence (page p on shard r covers positions
    [(p*n_shards+r)*page, ...)) and partial softmax stats are merged across
    the axis (flash-decode).
    """
    S, Bmax = block_tables.shape
    page = cache.shape[1]
    D = q.shape[-1]
    _check_table_alignment(Bmax, pages_per_block)
    n_blocks = Bmax // pages_per_block
    Bk = pages_per_block * page

    # On a TPU the Pallas kernel runs (identical math, explicit VMEM
    # tiling); the jnp path below is the CPU implementation.
    if merge_axis is None:
        from repro.kernels import ops as kops
        if kops.on_tpu():
            from repro.kernels.paged_attention import paged_flash_attention
            return paged_flash_attention(q, cache, block_tables,
                                         context_lens, q_positions)

    def kv_blk(i):
        tabs = jax.lax.dynamic_slice_in_dim(block_tables, i * pages_per_block,
                                            pages_per_block, axis=1)  # [S, pb]
        gathered = cache[tabs]                 # [S, pb, page, KH·2·D]
        kb, vb = split_kv(gathered.reshape(S, Bk, -1), D)   # [S, Bk, KH, D]
        base = (i * pages_per_block + jnp.arange(pages_per_block)) * page
        kpos = (base[:, None] + jnp.arange(page)[None, :]).reshape(Bk)  # [Bk]
        if shard_info is not None:
            shard_idx, n_shards = shard_info
            # interleaved sequence sharding: local page b = global page b*n+r
            gbase = ((i * pages_per_block + jnp.arange(pages_per_block))
                     * n_shards + shard_idx) * page
            kpos = (gbase[:, None] + jnp.arange(page)[None, :]).reshape(Bk)
        mask = (kpos[None, None, :] < context_lens[:, None, None]) & \
               (kpos[None, None, :] <= q_positions[:, :, None])
        return kb, vb, mask

    o, m, l = flash_attention_blocks(q, kv_blk, n_blocks, v_dim=D)
    if merge_axis is not None:
        o, m, l = merge_flash_partials(o, m, l, merge_axis)
    return finalize_flash(o, l, q.dtype)


def paged_attention_mla(
    q: jax.Array,                     # [S, C, H, dn + dr]
    cache: jax.Array,                 # [Pages, page, klr + dr]  (latent + rope)
    w_ukv: jax.Array,                 # [klr, H * (dn + dv)]
    block_tables: jax.Array,
    context_lens: jax.Array,
    q_positions: jax.Array,
    *,
    kv_lora_rank: int,
    qk_nope_dim: int,
    v_head_dim: int,
    pages_per_block: int = 8,
) -> jax.Array:
    """MLA: latent KV pages are expanded to per-head K/V *per block inside the
    flash scan* — the full expanded K/V never hits HBM (DeepSeek-V2 style,
    memory-bound decode becomes latent-read-bound)."""
    S, Bmax = block_tables.shape
    page = cache.shape[1]
    klr = kv_lora_rank
    dn, dv = qk_nope_dim, v_head_dim
    H = q.shape[-2]
    dr = q.shape[-1] - dn
    _check_table_alignment(Bmax, pages_per_block)
    n_blocks = Bmax // pages_per_block
    Bk = pages_per_block * page

    def kv_blk(i):
        tabs = jax.lax.dynamic_slice_in_dim(block_tables, i * pages_per_block,
                                            pages_per_block, axis=1)
        lat = cache[tabs].reshape(S, Bk, klr + dr)
        c_kv, k_rope = lat[..., :klr], lat[..., klr:]
        kv = (c_kv @ w_ukv).reshape(S, Bk, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (S, Bk, H, dr))],
            axis=-1)
        base = (i * pages_per_block + jnp.arange(pages_per_block)) * page
        kpos = (base[:, None] + jnp.arange(page)[None, :]).reshape(Bk)
        mask = (kpos[None, None, :] < context_lens[:, None, None]) & \
               (kpos[None, None, :] <= q_positions[:, :, None])
        return k, v, mask

    o, m, l = flash_attention_blocks(q, kv_blk, n_blocks, v_dim=dv)
    return finalize_flash(o, l, q.dtype)
