"""Serve-mode model execution: one pipeline-stage forward over a micro-batch
of prefill chunks + decode rows, with paged KV / recurrent-state caches.

Layouts (per pipeline stage, per data replica — both mesh axes are manual
inside the serving tick):
  prefill payload  xp [Sp, C, d]    (whisper: [Sp, Te + C, d], enc slice first)
  decode payload   xd [Sd, 1, d]
  paged KV         [R, pages, page, KH·2·hd]     (R = block repeat; lanes
                   head-major [KH, 2, hd]: each head's K then V)
  MLA latent KV    [R, pages, page, klr + dr]
  mamba state      conv [R, slots, dc-1, di], ssm [R, slots, di, ds]
  rwkv state       tm_x/cm_x [R, slots, d], wkv [R, slots, H, hk, hv]
  whisper enc      enc_h [slots, Te, d]  (stage-local encoder hidden cache)

The static bucket sizes (Sp, C, Sd, pages, ...) come from `ServeDims`; Token
Throttling keeps the real token counts near the bucket so the padding — the
TPU form of a pipeline bubble — stays small (DESIGN.md §2).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, BlockKind
from repro.models import attention as attn
from repro.models import ssm as ssm_lib
from repro.models import moe as moe_lib
from repro.models.layers import apply_mrope, apply_norm, apply_rope, mlp_apply, rmsnorm
from repro.models.transformer import _block_key, _heads

# Flash KV-block granularity (pages per gather step).  Read once at import —
# see `_pages_per_block` for why a live re-read is wrong.
PAGES_PER_BLOCK = int(os.environ.get("REPRO_PAGES_PER_BLOCK", "8"))

# KV-depth bucket divisors k -> depth step ⌈B/k⌉ (DESIGN.md §14).  "4,2,1"
# is the {⌈B/4⌉, ⌈B/2⌉, B} ladder; "1" disables depth bucketing.
DEPTH_DIVISORS: Tuple[int, ...] = tuple(
    int(x) for x in os.environ.get("REPRO_DEPTH_STEPS", "4,2,1").split(",")
    if x.strip())


@dataclass(frozen=True)
class ServeDims:
    """Static bucket sizes for one (arch, shape) serving cell, per replica."""

    Sp: int              # prefill sequences per tick (0 for decode-only cells)
    C: int               # prefill chunk bucket (tokens per prefill seq)
    Sd: int              # decode rows per tick
    pages: int           # KV pool size (pages) per replica, per layer
    page: int            # page size in tokens
    Bp: int              # max pages per prefill seq's block table
    Bd: int              # max pages per decode seq's block table
    slots: int           # recurrent-state / enc-cache sequence slots
    Te: int = 0          # whisper encoder bucket (0 for non-enc-dec)
    seq_shard: bool = False   # long-context: KV sequence sharded over `data`

    @property
    def prefill_width(self) -> int:
        return self.Te + self.C

    @property
    def rows(self) -> int:
        return self.Sp * self.prefill_width + self.Sd


def depth_steps(B: int, *, pages_per_block: Optional[int] = None,
                divisors: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Block-table depth buckets for a phase whose full table is `B` pages:
    {⌈B/k⌉ for k in `divisors`} rounded up to multiples of the flash gather
    granularity (`pages_per_block`), deduplicated, always including B.  A
    full width not divisible by `pages_per_block` gets no sub-buckets — the
    attention path requires the same divisibility at every width."""
    ppb = pages_per_block if pages_per_block is not None else _pages_per_block()
    if B <= 0 or ppb <= 0 or B % ppb != 0:
        return (B,)
    divisors = tuple(divisors) if divisors is not None else DEPTH_DIVISORS
    steps = {B}
    for k in divisors:
        if k > 0:
            need = -(-B // k)                       # ⌈B/k⌉ pages demanded
            steps.add(min(B, ppb * -(-need // ppb)))  # …rounded to blocks
    return tuple(sorted(steps))


def bucket_ladder(dims: ServeDims,
                  depth_divisors: Optional[Sequence[int]] = None
                  ) -> Tuple[ServeDims, ...]:
    """Fixed ladder of serve shapes for bucketed execution (DESIGN.md §12/§14).

    Three bucket dimensions, deduplicated: prefill-chunk buckets
    {0, ⌈C/4⌉, ⌈C/2⌉, C} × decode-row buckets {⌈Sd/4⌉, ⌈Sd/2⌉, Sd} × KV
    depth — the block-table widths Bp/Bd stepped per `depth_steps`.  One
    shared depth index scales both phases together (×len(steps) ladder
    growth, not the Bp×Bd cross product); a phase with no rows in an entry
    keeps its full table width, since its meta carries no live tables there.
    Every entry keeps the full `dims` cache geometry (pages/page/slots/Te
    untouched), so one KV pool, one parameter tree, and one carry buffer
    serve every program in the ladder.  The Sp=0 entries are the "0 prefill
    tokens" buckets; decode-only shapes keep C at its full value since the
    prefill payload has no rows there.  The fully-empty (Sp=0, Sd=0) shape
    is excluded — bubble ticks run in the smallest non-empty bucket.
    """
    def ceil_div(a: int, b: int) -> int:
        return -(-a // b)

    c_steps = sorted({max(1, ceil_div(dims.C, 4)),
                      max(1, ceil_div(dims.C, 2)), dims.C})
    d_steps = ([0] if dims.Sd == 0 else
               sorted({max(1, ceil_div(dims.Sd, 4)),
                       max(1, ceil_div(dims.Sd, 2)), dims.Sd}))
    bp_steps = depth_steps(dims.Bp, divisors=depth_divisors)
    bd_steps = depth_steps(dims.Bd, divisors=depth_divisors)
    n_depth = max(len(bp_steps), len(bd_steps))
    # shared depth index i = "fraction i of both phases"; the shorter
    # phase's list saturates at its full width
    depth_pairs = []
    for i in range(n_depth):
        pair = (bp_steps[min(i, len(bp_steps) - 1)],
                bd_steps[min(i, len(bd_steps) - 1)])
        if pair not in depth_pairs:
            depth_pairs.append(pair)
    ladder = []
    seen = set()
    for Sd_b in d_steps:
        variants = [(0, dims.C)]
        if dims.Sp > 0:
            variants += [(dims.Sp, c) for c in c_steps]
        for Sp_b, C_b in variants:
            for Bp_b, Bd_b in depth_pairs:
                bp = Bp_b if Sp_b > 0 else dims.Bp
                bd = Bd_b if Sd_b > 0 else dims.Bd
                key = (Sp_b, C_b, Sd_b, bp, bd)
                if key in seen or (Sp_b == 0 and Sd_b == 0):
                    continue
                seen.add(key)
                ladder.append(replace(dims, Sp=Sp_b, C=C_b, Sd=Sd_b,
                                      Bp=bp, Bd=bd))
    return tuple(ladder)


def select_bucket(ladder: Sequence[ServeDims], need_c: int, need_d: int,
                  need_bp: int = 0, need_bd: int = 0) -> ServeDims:
    """Smallest ladder entry covering a tick whose widest prefill chunk is
    `need_c` tokens, whose decode rows number `need_d`, and whose deepest
    prefill/decode block tables hold `need_bp`/`need_bd` live pages.
    Minimality is by padded row count (`rows`); ties break toward the
    narrower prefill bucket, the smaller decode bucket, then the shallower
    block tables.  Depth demands only bind for phases with rows (`need_c`
    resp. `need_d` nonzero): a phase with no rows reads no tables."""
    best: Optional[ServeDims] = None
    for b in ladder:
        covers = ((need_c == 0 or (b.Sp > 0 and b.C >= need_c
                                   and b.Bp >= need_bp))
                  and b.Sd >= need_d
                  and (need_d == 0 or b.Bd >= need_bd))
        if not covers:
            continue
        key = (b.rows, b.C, b.Sd, b.Bp, b.Bd)
        if best is None or key < (best.rows, best.C, best.Sd,
                                  best.Bp, best.Bd):
            best = b
    if best is None:
        raise ValueError(
            f"no bucket covers need_c={need_c}, need_d={need_d}, "
            f"need_bp={need_bp}, need_bd={need_bd} "
            f"(ladder max C={max(b.C for b in ladder)}, "
            f"Sd={max(b.Sd for b in ladder)}, "
            f"Bp={max(b.Bp for b in ladder)}, "
            f"Bd={max(b.Bd for b in ladder)})")
    return best


def _meta_field_defs(dims: ServeDims) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    Sp, C, Sd = dims.Sp, dims.C, dims.Sd
    return {
        "p_positions": ((Sp, C), jnp.int32),
        "p_chunk_lens": ((Sp,), jnp.int32),
        "p_context_lens": ((Sp,), jnp.int32),
        "p_block_tables": ((Sp, dims.Bp), jnp.int32),
        "p_slot_pages": ((Sp, C), jnp.int32),
        "p_slot_offsets": ((Sp, C), jnp.int32),
        "p_state_slots": ((Sp,), jnp.int32),
        "p_sample": ((Sp,), jnp.int32),        # 1 if chunk finishes prefill
        "d_positions": ((Sd,), jnp.int32),
        "d_context_lens": ((Sd,), jnp.int32),
        "d_block_tables": ((Sd, dims.Bd), jnp.int32),
        "d_slot_pages": ((Sd,), jnp.int32),
        "d_slot_offsets": ((Sd,), jnp.int32),
        "d_state_slots": ((Sd,), jnp.int32),
        "d_valid": ((Sd,), jnp.int32),
    }


def zero_meta(dims: ServeDims) -> Dict[str, jax.Array]:
    out = {}
    for k, (shape, dt) in _meta_field_defs(dims).items():
        fill = -1 if k in ("p_slot_pages", "d_slot_pages") else 0
        out[k] = jnp.full(shape, fill, dt)
    return out


def abstract_meta(dims: ServeDims, stages: int, stack: bool = True):
    return {
        k: jax.ShapeDtypeStruct(((stages,) + shape) if stack else shape, dt)
        for k, (shape, dt) in _meta_field_defs(dims).items()
    }


def meta_pspecs(dims: ServeDims):
    """stage dim manual; per-replica seq dims are sharded over `data`."""
    return {k: P("stage", "data") for k in _meta_field_defs(dims)}


# ----------------------------------------------------------------------------
# Cache construction
# ----------------------------------------------------------------------------

def block_cache_defs(cfg: ArchConfig, kind: BlockKind, dims: ServeDims,
                     repeat: int):
    """(shape, pspec) per cache array of one block group (no stage dim)."""
    R = repeat
    tp_heads = max(1, cfg.num_kv_heads)
    out: Dict[str, Tuple[Tuple[int, ...], P]] = {}
    if kind in (BlockKind.ATTN_MLP, BlockKind.ATTN_MOE, BlockKind.DEC_LAYER):
        # lane-dense: a token's K and V for every head on the minor axis, so
        # the stored layout is the kernel's tile layout (DESIGN.md §6); a
        # `tensor` split of the head-major lanes takes whole heads
        out["kv"] = ((R, dims.pages, dims.page, tp_heads * 2 * cfg.head_dim),
                     P(None, "data", None, "tensor"))
    elif kind == BlockKind.MLA_MLP:
        out["kv"] = ((R, dims.pages, dims.page,
                      cfg.kv_lora_rank + cfg.qk_rope_dim),
                     P(None, "data", None, None))
    elif kind in (BlockKind.MAMBA_MLP, BlockKind.MAMBA_MOE):
        di = cfg.mamba_d_inner
        out["conv"] = ((R, dims.slots, cfg.mamba_d_conv - 1, di),
                       P(None, "data", None, "tensor"))
        # the selective-scan state carries in f32 (recurrence precision)
        out["ssm"] = ((R, dims.slots, di, cfg.mamba_d_state),
                      P(None, "data", "tensor", None))
    elif kind == BlockKind.RWKV:
        H, hd = cfg.num_rwkv_heads, cfg.rwkv_head_dim
        out["tm_x"] = ((R, dims.slots, cfg.d_model), P(None, "data", None))
        out["cm_x"] = ((R, dims.slots, cfg.d_model), P(None, "data", None))
        # the WKV state carries in f32 (recurrence precision)
        out["wkv"] = ((R, dims.slots, H, hd, hd),
                      P(None, "data", "tensor", None, None))
    if kind == BlockKind.ENC_LAYER:
        pass  # encoder layers are stateless
    return out


def cache_defs(cfg: ArchConfig, dims: ServeDims):
    """Full cache tree of (shape, pspec) with leading stage dim."""
    S = cfg.plan.pp
    tree: Dict[str, Any] = {}
    for i, bs in enumerate(cfg.pattern):
        defs = block_cache_defs(cfg, bs.kind, dims, bs.repeat)
        if defs:
            tree[_block_key(i, bs)] = {
                k: ((S,) + shape, P(*(("stage",) + tuple(spec))))
                for k, (shape, spec) in defs.items()
            }
    if cfg.is_encoder_decoder:
        tree["enc_h"] = {"h": ((S, dims.slots, dims.Te, cfg.d_model),
                               P("stage", "data", None, None))}
    return tree


def _isdef(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], P)


F32_STATE_LEAVES = ("ssm", "wkv")    # recurrent states carry in f32


def cache_leaf_dtype(name: str, model_dtype) -> Any:
    return jnp.float32 if name in F32_STATE_LEAVES else model_dtype


def _map_caches_with_names(cfg, dims, fn):
    defs = cache_defs(cfg, dims)
    return {gk: {name: fn(name, leaf) for name, leaf in grp.items()}
            for gk, grp in defs.items()}


def init_caches(cfg: ArchConfig, dims: ServeDims, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return _map_caches_with_names(
        cfg, dims,
        lambda name, leaf: jnp.zeros(leaf[0], cache_leaf_dtype(name, dtype)))


def abstract_caches(cfg: ArchConfig, dims: ServeDims, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return _map_caches_with_names(
        cfg, dims,
        lambda name, leaf: jax.ShapeDtypeStruct(
            leaf[0], cache_leaf_dtype(name, dtype)))


def cache_pspecs(cfg: ArchConfig, dims: ServeDims):
    return jax.tree.map(lambda leaf: leaf[1], cache_defs(cfg, dims),
                        is_leaf=_isdef)


# ----------------------------------------------------------------------------
# Serve-mode attention helpers
# ----------------------------------------------------------------------------

def _qkv_rows(cfg, p, x, positions, prefix=""):
    """x [S, T, d], positions [S, T] -> q [S,T,H,hd], k/v [S,T,KH,hd]."""
    q = x @ p[f"{prefix}wq"]
    k = x @ p[f"{prefix}wk"]
    v = x @ p[f"{prefix}wv"]
    if cfg.qkv_bias and f"{prefix}bq" in p:
        q, k, v = q + p[f"{prefix}bq"], k + p[f"{prefix}bk"], v + p[f"{prefix}bv"]
    q = _heads(q, cfg.num_heads, cfg.head_dim)
    k = _heads(k, cfg.num_kv_heads, cfg.head_dim)
    v = _heads(v, cfg.num_kv_heads, cfg.head_dim)
    if cfg.mrope:
        pos3 = jnp.broadcast_to(positions, (3,) + positions.shape)
        q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pages_per_block() -> int:
    """Flash KV-block granularity (pages per gather step) — §Perf knob.
    Read once at import (`PAGES_PER_BLOCK` below): this value participates
    in traced shape math, so a per-call env read would burn host time in
    the tick hot path and a mid-process change would silently split the
    jit cache."""
    return PAGES_PER_BLOCK


def _paged_self_attention(cfg, p, xs, cache, meta, dims: ServeDims,
                          is_prefill: bool, prefix=""):
    """Project, write pages, attend.  Returns (attn_out, new_cache)."""
    if is_prefill:
        positions = meta["p_positions"]
        valid = (jnp.arange(dims.C)[None, :] < meta["p_chunk_lens"][:, None])
        tables, ctx = meta["p_block_tables"], meta["p_context_lens"]
        pages, offs = meta["p_slot_pages"], meta["p_slot_offsets"]
    else:
        positions = meta["d_positions"][:, None]
        valid = (meta["d_valid"] > 0)[:, None]
        tables, ctx = meta["d_block_tables"], meta["d_context_lens"]
        pages, offs = meta["d_slot_pages"][:, None], meta["d_slot_offsets"][:, None]

    with jax.named_scope("qkv"):
        q, k, v = _qkv_rows(cfg, p, xs, positions, prefix)
    with jax.named_scope("kv_write"):
        cache = attn.write_kv_pages(cache, attn.pack_kv(k, v), pages, offs,
                                    valid)
    merge_axis = "data" if (dims.seq_shard and not is_prefill) else None
    shard_info = None
    if merge_axis is not None:
        shard_info = (jax.lax.axis_index("data"), jax.lax.psum(1, "data"))
    with jax.named_scope("attention"):
        o = attn.paged_attention(q, cache, tables, ctx, positions,
                                 pages_per_block=_pages_per_block(),
                                 merge_axis=merge_axis, shard_info=shard_info)
    o = o.reshape(o.shape[:-2] + (-1,)) @ p[f"{prefix}wo"]
    return o, cache


def _paged_mla_attention(cfg, p, xs, cache, meta, dims: ServeDims,
                         is_prefill: bool):
    S, T, _ = xs.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    klr = cfg.kv_lora_rank
    if is_prefill:
        positions = meta["p_positions"]
        valid = (jnp.arange(dims.C)[None, :] < meta["p_chunk_lens"][:, None])
        tables, ctx = meta["p_block_tables"], meta["p_context_lens"]
        pages, offs = meta["p_slot_pages"], meta["p_slot_offsets"]
    else:
        positions = meta["d_positions"][:, None]
        valid = (meta["d_valid"] > 0)[:, None]
        tables, ctx = meta["d_block_tables"], meta["d_context_lens"]
        pages, offs = meta["d_slot_pages"][:, None], meta["d_slot_offsets"][:, None]

    with jax.named_scope("qkv"):
        cq = rmsnorm(xs @ p["w_dq"], p["q_norm_g"], cfg.norm_eps)
        q = (cq @ p["w_uq"]).reshape(S, T, H, dn + dr)
        q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        ckv_full = xs @ p["w_dkv"]
        ckv = rmsnorm(ckv_full[..., :klr], p["kv_norm_g"], cfg.norm_eps)
        k_rope = apply_rope(ckv_full[..., None, klr:], positions,
                            cfg.rope_theta)[..., 0, :]
        lat = jnp.concatenate([ckv, k_rope], axis=-1)      # [S, T, klr+dr]
    with jax.named_scope("kv_write"):
        cache = attn.write_kv_pages(cache, lat, pages, offs, valid)
    with jax.named_scope("attention"):
        o = attn.paged_attention_mla(
            q, cache, p["w_ukv"], tables, ctx, positions,
            kv_lora_rank=klr, qk_nope_dim=dn, v_head_dim=dv,
            pages_per_block=_pages_per_block())
    return o.reshape(S, T, H * dv) @ p["wo"], cache


def _gathered_state_step(mixer_fn, xs, state_arrays, state_slots, chunk_lens):
    """Gather per-seq recurrent state, run the mixer, scatter back.

    state_arrays: dict name -> [slots, ...]; state_slots [S]; returns
    (out, new_state_arrays)."""
    gathered = {k: v[state_slots] for k, v in state_arrays.items()}
    out, new_state = mixer_fn(xs, gathered)
    updated = {}
    for k, v in state_arrays.items():
        upd = new_state[k]
        updated[k] = v.at[state_slots].set(upd, mode="drop")
    return out, updated

# ----------------------------------------------------------------------------
# Per-kind serve block application
# ----------------------------------------------------------------------------

def _mamba_serve(cfg, p, xs, caches, state_slots, chunk_lens):
    """xs [S, T, d]; caches {conv [slots, dc-1, di], ssm [slots, di, ds]}.
    chunk_lens masks padded rows (dt := 0 -> state frozen)."""
    S, T, _ = xs.shape
    valid = (jnp.arange(T)[None, :] < chunk_lens[:, None])

    def mixer(x, st):
        state = ssm_lib.MambaState(conv=st["conv"], ssm=st["ssm"])
        # mask padded rows by zeroing the input (dt(0)=softplus(bias) != 0, so
        # also freeze via masked dt below); simplest correct: zero input rows
        # and rebuild conv/ssm state from valid length.
        xm = jnp.where(valid[..., None], x, 0)
        out, new = ssm_lib.mamba_mixer(
            xm, p, d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv,
            state=state, valid=valid, chunk_lens=chunk_lens)
        return out, {"conv": new.conv, "ssm": new.ssm}

    out, updated = _gathered_state_step(mixer, xs, caches, state_slots,
                                        chunk_lens)
    return jnp.where(valid[..., None], out, 0), updated


def _rwkv_serve(cfg, p, xs, caches, state_slots, chunk_lens):
    S, T, _ = xs.shape
    valid = (jnp.arange(T)[None, :] < chunk_lens[:, None])

    def mixer(x, st):
        state = ssm_lib.RWKVState(tm_x=st["tm_x"], cm_x=st["cm_x"],
                                  wkv=st["wkv"])
        out, new = ssm_lib.rwkv_block(
            x, p, head_dim=cfg.rwkv_head_dim, norm_eps=cfg.norm_eps,
            state=state, valid=valid, chunk_lens=chunk_lens)
        return out, {"tm_x": new.tm_x, "cm_x": new.cm_x, "wkv": new.wkv}

    out, updated = _gathered_state_step(mixer, xs, caches, state_slots,
                                        chunk_lens)
    return jnp.where(valid[..., None], out, 0), updated


def block_apply_serve(cfg: ArchConfig, kind: BlockKind, p, xp, xd, cache,
                      meta, dims: ServeDims, enc_cache=None):
    """One block over the stage's micro-batch.

    xp [Sp, W, d] prefill payload (W = Te + C for whisper, C otherwise),
    xd [Sd, 1, d] decode rows.  Returns (xp, xd, new_cache, new_enc_cache)."""
    eps = cfg.norm_eps

    def norm(name, h):
        keys = {"g": p[f"{name}_g"]}
        if f"{name}_b" in p:
            keys["b"] = p[f"{name}_b"]
        return apply_norm(h, keys, cfg.norm, eps)

    new_cache = cache
    Sp, Sd = dims.Sp, dims.Sd
    has_p, has_d = Sp > 0, Sd > 0

    if kind == BlockKind.RWKV:
        # time-mix + channel-mix as one fused block (own norms inside)
        if has_p:
            yp, st = _rwkv_serve(cfg, p, xp,
                                 {k: cache[k] for k in ("tm_x", "cm_x", "wkv")},
                                 meta["p_state_slots"], meta["p_chunk_lens"])
            xp = yp
            new_cache = st
        if has_d:
            yd, st2 = _rwkv_serve(cfg, p, xd,
                                  {k: (new_cache if has_p else cache)[k]
                                   for k in ("tm_x", "cm_x", "wkv")},
                                  meta["d_state_slots"], meta["d_valid"])
            xd = yd
            new_cache = st2
        return xp, xd, new_cache, enc_cache

    if kind in (BlockKind.ENC_LAYER, BlockKind.DEC_LAYER):
        Te = dims.Te
        enc = xp[:, :Te] if has_p else None
        dec = xp[:, Te:] if has_p else None
        if kind == BlockKind.ENC_LAYER:
            if has_p:
                h = norm("ln1", enc)
                pos = jnp.broadcast_to(jnp.arange(Te), (Sp, Te))
                q, k, v = _qkv_rows(cfg, p, h, pos)
                o = attn.cross_attention(q, k, v)           # bidirectional
                enc = enc + o.reshape(Sp, Te, -1) @ p["wo"]
                h = norm("ln2", enc)
                enc = enc + mlp_apply(h, p, cfg.act)
                xp = jnp.concatenate([enc, dec], axis=1)
            return xp, xd, new_cache, enc_cache
        # DEC_LAYER: causal paged self-attn + cross-attn
        if has_p:
            h = norm("ln1", dec)
            o, new_cache = _paged_self_attention(
                cfg, p, h, cache["kv"], meta, dims, is_prefill=True)
            new_cache = {"kv": new_cache}
            dec = dec + o
            h = norm("ln3", dec)
            q = _heads(h @ p["x_wq"] + p.get("x_bq", 0.0), cfg.num_heads,
                       cfg.head_dim)
            k = _heads(enc @ p["x_wk"] + p.get("x_bk", 0.0),
                       cfg.num_kv_heads, cfg.head_dim)
            v = _heads(enc @ p["x_wv"] + p.get("x_bv", 0.0),
                       cfg.num_kv_heads, cfg.head_dim)
            o = attn.cross_attention(q, k, v)
            dec = dec + o.reshape(Sp, dims.C, -1) @ p["x_wo"]
            h = norm("ln2", dec)
            dec = dec + mlp_apply(h, p, cfg.act)
            xp = jnp.concatenate([enc, dec], axis=1)
        if has_d:
            kvc = new_cache["kv"] if isinstance(new_cache, dict) and "kv" in new_cache else cache["kv"]
            h = norm("ln1", xd)
            o, kvc = _paged_self_attention(cfg, p, h, kvc, meta, dims,
                                           is_prefill=False)
            new_cache = {"kv": kvc}
            xd = xd + o
            # cross-attention against the cached stage-local encoder hidden
            h = norm("ln3", xd)
            src = enc_cache[meta["d_state_slots"]]            # [Sd, Te, d]
            q = _heads(h @ p["x_wq"] + p.get("x_bq", 0.0), cfg.num_heads,
                       cfg.head_dim)
            k = _heads(src @ p["x_wk"] + p.get("x_bk", 0.0),
                       cfg.num_kv_heads, cfg.head_dim)
            v = _heads(src @ p["x_wv"] + p.get("x_bv", 0.0),
                       cfg.num_kv_heads, cfg.head_dim)
            o = attn.cross_attention(q, k, v)
            xd = xd + o.reshape(Sd, 1, -1) @ p["x_wo"]
            h = norm("ln2", xd)
            xd = xd + mlp_apply(h, p, cfg.act)
        return xp, xd, new_cache, enc_cache

    # ---- standard mixer + ffn blocks --------------------------------------
    if kind in (BlockKind.MAMBA_MLP, BlockKind.MAMBA_MOE):
        st_keys = ("conv", "ssm")
        if has_p:
            h = norm("ln1", xp)
            o, st = _mamba_serve(cfg, p, h, {k: cache[k] for k in st_keys},
                                 meta["p_state_slots"], meta["p_chunk_lens"])
            xp = xp + o
            new_cache = dict(st)
        if has_d:
            base = new_cache if has_p else cache
            h = norm("ln1", xd)
            o, st = _mamba_serve(cfg, p, h, {k: base[k] for k in st_keys},
                                 meta["d_state_slots"], meta["d_valid"])
            xd = xd + o
            new_cache = dict(st)
    elif kind == BlockKind.MLA_MLP:
        kvc = cache["kv"]
        if has_p:
            h = norm("ln1", xp)
            o, kvc = _paged_mla_attention(cfg, p, h, kvc, meta, dims, True)
            xp = xp + o
        if has_d:
            h = norm("ln1", xd)
            o, kvc = _paged_mla_attention(cfg, p, h, kvc, meta, dims, False)
            xd = xd + o
        new_cache = {"kv": kvc}
    else:  # ATTN_MLP / ATTN_MOE
        kvc = cache["kv"]
        if has_p:
            h = norm("ln1", xp)
            o, kvc = _paged_self_attention(cfg, p, h, kvc, meta, dims, True)
            xp = xp + o
        if has_d:
            h = norm("ln1", xd)
            o, kvc = _paged_self_attention(cfg, p, h, kvc, meta, dims, False)
            xd = xd + o
        new_cache = {"kv": kvc}

    # ffn over all rows (flattened); static-bucket padding rows are masked
    # out of MoE routing so they never consume expert capacity
    parts, valid_parts = [], []
    if has_p:
        parts.append(norm("ln2", xp).reshape(-1, cfg.d_model))
        pv = (jnp.arange(xp.shape[1])[None, :]
              < (dims.Te + meta["p_chunk_lens"])[:, None])
        valid_parts.append(pv.reshape(-1))
    if has_d:
        parts.append(norm("ln2", xd).reshape(-1, cfg.d_model))
        valid_parts.append((meta["d_valid"] > 0))
    flat = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    with jax.named_scope("mlp"):
        if kind in (BlockKind.ATTN_MOE, BlockKind.MAMBA_MOE):
            ep = "data" if cfg.plan.ep_over_data else None
            row_valid = (jnp.concatenate(valid_parts)
                         if len(valid_parts) > 1 else valid_parts[0])
            y, _ = moe_lib.moe_apply(flat, p, top_k=cfg.num_experts_per_tok,
                                     ep_axis=ep,
                                     capacity_factor=cfg.moe_capacity_factor,
                                     row_valid=row_valid)
        else:
            y = mlp_apply(flat, p, cfg.act)
    off = 0
    if has_p:
        n = Sp * xp.shape[1]
        xp = xp + y[off:off + n].reshape(xp.shape)
        off += n
    if has_d:
        xd = xd + y[off:].reshape(xd.shape)
    return xp, xd, new_cache, enc_cache


def stage_forward_serve(cfg: ArchConfig, stage_params, caches, xp, xd, meta,
                        dims: ServeDims):
    """Apply one stage's blocks to its resident micro-batch (inside the
    manual {'stage','data'} shard_map).  Returns (xp, xd, new_caches)."""
    stage_idx = jax.lax.axis_index("stage")
    layer_offset = 0
    new_caches = dict(caches) if caches else {}
    enc_cache = caches.get("enc_h", {}).get("h") if caches else None
    # whisper: cache this stage's encoder hidden for decode cross-attention
    if cfg.is_encoder_decoder and dims.Sp > 0 and enc_cache is not None:
        pass  # written after the encoder blocks below

    for i, bs in enumerate(cfg.pattern):
        key = _block_key(i, bs)
        p = stage_params[key]
        cache_i = caches.get(key) if caches else None

        def apply_one(carry, pl, cl, local_i, kind=bs.kind, off=layer_offset):
            cxp, cxd, cenc = carry
            g = stage_idx * cfg.layers_per_stage + off + local_i
            active = jnp.where(g < cfg.num_layers, 1.0, 0.0)
            yp, yd, new_cl, cenc = block_apply_serve(
                cfg, kind, pl, cxp, cxd, cl, meta, dims, enc_cache=cenc)
            a = active.astype(cxp.dtype if dims.Sp else cxd.dtype)
            if dims.Sp:
                yp = cxp + a * (yp - cxp)
            if dims.Sd:
                yd = cxd + a * (yd - cxd)
            # NOTE: padded layers' cache writes land in their *own* [R, ...]
            # slice and are never read (outputs masked above) — no freeze
            # needed, and freezing would touch the full KV pool every layer.
            return (yp, yd, cenc), new_cl

        if bs.repeat == 1:
            p1 = jax.tree.map(lambda a: a[0], p)
            c1 = jax.tree.map(lambda a: a[0], cache_i) if cache_i else None
            (xp, xd, enc_cache), nc = apply_one((xp, xd, enc_cache), p1, c1, 0)
            if cache_i is not None and nc is not None:
                new_caches[key] = jax.tree.map(lambda a: a[None], nc)
        else:
            # layer loop with the stacked cache in the carry: each layer
            # reads its slice and writes it back in place, so the pool is
            # never double-buffered, and the layer body (with its kernel
            # calls) is compiled once instead of once per layer
            def scan_body(carry, inp):
                act, acc = carry
                pl, li = inp
                with jax.named_scope("kv_slice"):
                    cl = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, li, 0, keepdims=False), acc) if acc else None
                act, nc = apply_one(act, pl, cl, li)
                if acc:
                    with jax.named_scope("kv_update"):
                        acc = jax.tree.map(
                            lambda a, u: jax.lax.dynamic_update_index_in_dim(
                                a, u, li, 0), acc, nc)
                return (act, acc), None

            with jax.named_scope("layers"):
                ((xp, xd, enc_cache), acc), _ = jax.lax.scan(
                    scan_body, ((xp, xd, enc_cache), cache_i),
                    (p, jnp.arange(bs.repeat)))
            if acc:
                new_caches[key] = acc
        layer_offset += bs.repeat
        # whisper: after the encoder group, snapshot enc hidden into the cache
        if cfg.is_encoder_decoder and bs.kind == BlockKind.ENC_LAYER \
                and enc_cache is not None and dims.Sp > 0:
            slots = meta["p_state_slots"]
            upd = xp[:, :dims.Te]
            write = (meta["p_sample"] + jnp.zeros_like(slots)) >= 0  # prefill ticks
            tgt = jnp.where(meta["p_chunk_lens"] > 0, slots, -1)
            enc_cache = enc_cache.at[tgt].set(upd, mode="drop")

    if "enc_h" in new_caches and enc_cache is not None:
        new_caches["enc_h"] = {"h": enc_cache}
    return xp, xd, new_caches
