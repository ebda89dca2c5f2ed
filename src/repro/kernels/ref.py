"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_flash_attention_ref(
    q: jax.Array,             # [S, TQ, H, D]
    kv_pages: jax.Array,      # [P, page, KH·2·D], lanes [KH, 2, D]
    block_tables: jax.Array,  # [S, B]
    context_lens: jax.Array,  # [S]
    q_positions: jax.Array,   # [S, TQ]
) -> jax.Array:
    S, TQ, H, D = q.shape
    page = kv_pages.shape[1]
    KH = kv_pages.shape[-1] // (2 * D)
    B = block_tables.shape[1]
    G = H // KH
    gathered = kv_pages[block_tables]                  # [S, B, page, KH·2·D]
    kv = gathered.reshape(S, B * page, KH, 2, D).astype(jnp.float32)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    kpos = jnp.arange(B * page)
    mask = (kpos[None, None, :] < context_lens[:, None, None]) & \
           (kpos[None, None, :] <= q_positions[:, :, None])     # [S, TQ, Bp]
    qf = q.astype(jnp.float32).reshape(S, TQ, KH, G, D)
    scores = jnp.einsum("sqhgd,skhd->sqhgk", qf, k) * (D ** -0.5)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("sqhgk,skhd->sqhgd", p, v)
    return out.reshape(S, TQ, H, D).astype(q.dtype)


def rwkv6_scan_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,  # [B, T, H, D]
    u: jax.Array,                                            # [H, D]
) -> jax.Array:
    B, T, H, D = r.shape

    def step(S, inp):
        r_t, k_t, v_t, w_t = (x.astype(jnp.float32) for x in inp)
        kv = k_t[..., :, None] * v_t[..., None, :]    # [B, H, D, D]
        o = jnp.einsum("bhk,bhkv->bhv", r_t,
                       S + u[None].astype(jnp.float32)[..., :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, o

    S0 = jnp.zeros((B, H, D, D), jnp.float32)
    _, os = jax.lax.scan(step, S0,
                         tuple(jnp.moveaxis(x, 1, 0) for x in (r, k, v, w)))
    return jnp.moveaxis(os, 0, 1).astype(r.dtype)


def fused_moe_ffn_ref(x, w_gate, w_up, w_down):
    """x [E, C, d]; weights [E, d, ff] / [E, ff, d]."""
    g = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w_gate.astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w_up.astype(jnp.float32))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(jnp.float32))
    return out.astype(x.dtype)


def mamba_scan_ref(dA, dBx, C):
    """Sequential oracle: h_t = dA_t*h + dBx_t ; y_t = C_t . h_t.
    dA/dBx [B, T, di, ds]; C [B, T, ds] -> y [B, T, di]."""
    B, T, di, ds = dA.shape

    def step(h, inp):
        dA_t, dBx_t, C_t = (x.astype(jnp.float32) for x in inp)
        h = dA_t * h + dBx_t
        return h, jnp.einsum("bcs,bs->bc", h, C_t)

    h0 = jnp.zeros((B, di, ds), jnp.float32)
    _, ys = jax.lax.scan(step, h0,
                         tuple(jnp.moveaxis(x, 1, 0) for x in (dA, dBx, C)))
    return jnp.moveaxis(ys, 0, 1).astype(dA.dtype)
