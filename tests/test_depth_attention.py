"""Depth-bucketed paged attention is bit-identical to full-width (PR 8).

The depth bucket cuts the block-table width to the smallest ladder step
covering the pages actually in use; pages past a sequence's context hold no
in-context keys, so every flash update they produce is exactly zero
(NEG_INF scores underflow to p == 0.0 with alpha == 1.0).  That makes
dropping them *bit*-identical — asserted here with exact equality, not
tolerances — for the jnp path, the interpret-mode Pallas kernel (which also
skips dead pages inside the full-width walk), and the MLA path (jnp-only,
checked against a dense oracle too).
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:      # pragma: no cover - exercised on minimal installs
    HAS_HYPOTHESIS = False

from repro.kernels.paged_attention import paged_flash_attention
from repro.models.attention import paged_attention, paged_attention_mla
from repro.models.serve import depth_steps

S, H, KH, D, PAGE, B, PPB = 3, 4, 2, 16, 8, 8, 2
KLR, DN, DV, DR = 8, 8, 8, 4


def _case(seed, ctx_max=None, TQ=1):
    """Random q/cache/tables with every row holding real context."""
    rng = np.random.default_rng(seed)
    P = S * B + 2
    ctx_max = ctx_max or B * PAGE
    q = jnp.asarray(rng.normal(size=(S, TQ, H, D)), jnp.float32)
    cache = jnp.asarray(rng.normal(size=(P, PAGE, KH * 2 * D)), jnp.float32)
    tables = np.zeros((S, B), np.int32)
    ctx = rng.integers(TQ, ctx_max + 1, S).astype(np.int32)
    for s in range(S):
        live = -(-int(ctx[s]) // PAGE)
        tables[s, :live] = rng.choice(P, live, replace=False)
    qpos = jnp.asarray(ctx[:, None] - TQ + np.arange(TQ)[None, :], jnp.int32)
    return q, cache, jnp.asarray(tables), jnp.asarray(ctx), qpos


def _sliced_width(ctx, steps):
    need = max(-(-int(c) // PAGE) for c in np.asarray(ctx))
    return min(w for w in steps if w >= need)


def test_jnp_depth_slice_bit_identical():
    steps = depth_steps(B, pages_per_block=PPB)
    for seed in range(4):
        q, cache, tables, ctx, qpos = _case(seed, ctx_max=3 * PAGE, TQ=4)
        w = _sliced_width(ctx, steps)
        assert w < B, "case must actually shrink the table"
        full = paged_attention(q, cache, tables, ctx, qpos,
                               pages_per_block=PPB)
        cut = paged_attention(q, cache, tables[:, :w], ctx, qpos,
                              pages_per_block=PPB)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))


def test_pallas_depth_slice_bit_identical():
    for seed in range(3):
        q, cache, tables, ctx, qpos = _case(seed, ctx_max=3 * PAGE)
        need = max(-(-int(c) // PAGE) for c in np.asarray(ctx))
        full = paged_flash_attention(q, cache, tables, ctx, qpos,
                                     interpret=True)
        cut = paged_flash_attention(q, cache, tables[:, :need], ctx, qpos,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))


def test_pallas_dead_pages_never_read():
    """Corrupting the KV content *and table entries* of every dead page must
    not change the output: the kernel's clamped index_map never fetches them
    and the pl.when guard never touches their FLOPs."""
    q, cache, tables, ctx, qpos = _case(7, ctx_max=2 * PAGE)
    out_a = paged_flash_attention(q, cache, tables, ctx, qpos, interpret=True)
    cache2 = np.asarray(cache).copy()
    tables2 = np.asarray(tables).copy()
    for s in range(S):
        live = -(-int(ctx[s]) // PAGE)
        for b in range(live, B):
            cache2[tables2[s, b]] = np.nan     # poison the dead page content
            tables2[s, b] = (s + b) % cache2.shape[0]   # and the indirection
    out_b = paged_flash_attention(q, jnp.asarray(cache2),
                                  jnp.asarray(tables2), ctx, qpos,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def test_pallas_padded_row_outputs_zeros():
    """A ctx=0 padding row has no live page: the guard skips every update and
    finalize emits exact zeros (previously garbage; never read either way)."""
    q, cache, tables, ctx, qpos = _case(11)
    ctx = jnp.asarray(np.where(np.arange(S) == 0, 0, np.asarray(ctx)),
                      jnp.int32)
    out = np.asarray(paged_flash_attention(q, cache, tables, ctx, qpos,
                                           interpret=True))
    assert np.all(out[0] == 0.0)
    assert np.all(np.isfinite(out))


def _mla_case(seed, TQ=1):
    rng = np.random.default_rng(seed)
    P = S * B + 2
    q = jnp.asarray(rng.normal(size=(S, TQ, H, DN + DR)), jnp.float32)
    cache = jnp.asarray(rng.normal(size=(P, PAGE, KLR + DR)), jnp.float32)
    w_ukv = jnp.asarray(rng.normal(size=(KLR, H * (DN + DV))) * 0.3,
                        jnp.float32)
    tables = np.zeros((S, B), np.int32)
    ctx = rng.integers(TQ, 3 * PAGE + 1, S).astype(np.int32)
    for s in range(S):
        live = -(-int(ctx[s]) // PAGE)
        tables[s, :live] = rng.choice(P, live, replace=False)
    qpos = jnp.asarray(ctx[:, None] - TQ + np.arange(TQ)[None, :], jnp.int32)
    return q, cache, w_ukv, jnp.asarray(tables), jnp.asarray(ctx), qpos


def _mla_dense_ref(q, cache, w_ukv, tables, ctx, qpos):
    """Dense oracle: gather + expand the whole context, plain softmax."""
    q, cache, w_ukv = map(np.asarray, (q, cache, w_ukv))
    tables, ctx, qpos = map(np.asarray, (tables, ctx, qpos))
    S_, TQ = q.shape[:2]
    out = np.zeros((S_, TQ, H, DV), np.float32)
    for s in range(S_):
        lat = cache[tables[s]].reshape(B * PAGE, KLR + DR)
        c_kv, k_rope = lat[:, :KLR], lat[:, KLR:]
        kv = (c_kv @ w_ukv).reshape(B * PAGE, H, DN + DV)
        k = np.concatenate(
            [kv[..., :DN], np.broadcast_to(k_rope[:, None, :],
                                           (B * PAGE, H, DR))], axis=-1)
        v = kv[..., DN:]
        kpos = np.arange(B * PAGE)
        scale = (DN + DR) ** -0.5
        for t in range(TQ):
            mask = (kpos < ctx[s]) & (kpos <= qpos[s, t])
            sc = np.einsum("hd,khd->hk", q[s, t], k) * scale
            sc = np.where(mask[None, :], sc, -np.inf)
            w = np.exp(sc - sc.max(axis=-1, keepdims=True))
            w /= w.sum(axis=-1, keepdims=True)
            out[s, t] = np.einsum("hk,khd->hd", w, v)
    return out


def test_mla_depth_slice_bit_identical_and_matches_dense():
    steps = depth_steps(B, pages_per_block=PPB)
    for seed in range(3):
        q, cache, w_ukv, tables, ctx, qpos = _mla_case(seed, TQ=2)
        w = _sliced_width(ctx, steps)
        assert w < B
        kw = dict(kv_lora_rank=KLR, qk_nope_dim=DN, v_head_dim=DV,
                  pages_per_block=PPB)
        full = paged_attention_mla(q, cache, w_ukv, tables, ctx, qpos, **kw)
        cut = paged_attention_mla(q, cache, w_ukv, tables[:, :w], ctx, qpos,
                                  **kw)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))
        dense = _mla_dense_ref(q, cache, w_ukv, tables, ctx, qpos)
        np.testing.assert_allclose(np.asarray(full), dense, atol=3e-5)


def test_misaligned_width_raises_clear_error():
    q, cache, tables, ctx, qpos = _case(0)
    with pytest.raises(ValueError, match="REPRO_PAGES_PER_BLOCK"):
        paged_attention(q, cache, tables[:, :B - 1], ctx, qpos,
                        pages_per_block=PPB)


if HAS_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           ctx_pages=st.integers(1, B))
    def test_depth_slice_property(seed, ctx_pages):
        """Any slice width covering the live pages gives bit-identical
        outputs on both execution paths (jnp flash scan and interpret-mode
        Pallas), for random contexts and tables."""
        rng = np.random.default_rng(seed)
        q, cache, tables, _, _ = _case(seed)
        ctx = jnp.asarray(
            rng.integers(max((ctx_pages - 1) * PAGE, 1), ctx_pages * PAGE + 1,
                         S), jnp.int32)
        qpos = jnp.asarray(np.asarray(ctx)[:, None] - 1, jnp.int32)
        steps = depth_steps(B, pages_per_block=PPB)
        w = _sliced_width(ctx, steps)
        full = paged_attention(q, cache, tables, ctx, qpos,
                               pages_per_block=PPB)
        cut = paged_attention(q, cache, tables[:, :w], ctx, qpos,
                              pages_per_block=PPB)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))
        need = max(-(-int(c) // PAGE) for c in np.asarray(ctx))
        k_full = paged_flash_attention(q, cache, tables, ctx, qpos,
                                       interpret=True)
        k_cut = paged_flash_attention(q, cache, tables[:, :need], ctx, qpos,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(k_full), np.asarray(k_cut))
