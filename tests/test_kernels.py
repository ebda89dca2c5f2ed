"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.moe_gemm import fused_moe_ffn
from repro.kernels.paged_attention import paged_flash_attention
from repro.kernels.rwkv6_scan import rwkv6_chunked_scan
from repro.models import attention as attn


@pytest.mark.parametrize("S,TQ,H,KH,D,page,B", [
    (2, 1, 4, 2, 64, 8, 4),        # decode, GQA
    (1, 16, 4, 4, 128, 8, 4),      # prefill chunk, MHA
    (3, 8, 8, 2, 64, 16, 8),       # prefill, deep tables
    (2, 1, 8, 8, 128, 8, 8),       # decode, MHA, D=128
    (2, 1, 16, 16, 64, 16, 4),     # decode, MHA, D=64 (Qwen1.5-0.5B heads)
    (1, 8, 16, 8, 128, 16, 4),     # prefill, GQA, D=128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_vs_oracle(S, TQ, H, KH, D, page, B, dtype):
    rng = np.random.default_rng(hash((S, TQ, H, D)) % 2**31)
    P = S * B + 2
    q = jnp.asarray(rng.normal(size=(S, TQ, H, D)), dtype)
    kv = jnp.asarray(rng.normal(size=(P, page, KH * 2 * D)), dtype)
    tables = jnp.asarray(rng.permutation(P)[: S * B].reshape(S, B), jnp.int32)
    ctx = jnp.asarray(rng.integers(TQ, B * page + 1, S), jnp.int32)
    qpos = jnp.asarray(ctx[:, None] - TQ + np.arange(TQ)[None, :], jnp.int32)
    out_k = paged_flash_attention(q, kv, tables, ctx, qpos, interpret=True,
                                  q_block=min(8, TQ))
    out_r = ref.paged_flash_attention_ref(q, kv, tables, ctx, qpos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol)


def test_paged_flash_respects_context_len():
    """Tokens beyond context_lens must not contribute (garbage pages)."""
    rng = np.random.default_rng(0)
    S, TQ, H, KH, D, page, B = 1, 1, 2, 2, 64, 8, 4
    q = jnp.asarray(rng.normal(size=(S, TQ, H, D)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(8, page, KH * 2 * D)), jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    qpos = jnp.asarray([[9]], jnp.int32)
    out_a = paged_flash_attention(q, kv, tables, jnp.asarray([10]), qpos,
                                  interpret=True)
    # corrupt pages beyond ctx=10: output must not change
    kv2 = kv.at[2:].set(1e4)
    out_b = paged_flash_attention(q, kv2, tables, jnp.asarray([10]), qpos,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-6)


@pytest.mark.parametrize("T", [1, 8], ids=["decode", "prefill"])
@pytest.mark.parametrize("D", [64, 128])
def test_kv_pages_round_trip(T, D):
    """`write_kv_pages` puts each valid row's heads in the lanes the kernel
    reads (head kh's K at [2·kh·D, (2·kh+1)·D), its V in the next D) and
    touches nothing else, padded rows included; the jnp gather returns the
    K and V rows written."""
    rng = np.random.default_rng(D + T)
    S, KH, page, B = 3, 4, 4, 4
    P = S * B                                           # every page in use
    k = np.asarray(jnp.asarray(rng.normal(size=(S, T, KH, D)), jnp.bfloat16),
                   np.float32)
    v = np.asarray(jnp.asarray(rng.normal(size=(S, T, KH, D)), jnp.bfloat16),
                   np.float32)
    tables = rng.permutation(P).reshape(S, B).astype(np.int32)
    start = rng.integers(0, B * page - T + 1, S)        # first row's position
    pos = start[:, None] + np.arange(T)[None, :]        # [S, T]
    valid = np.arange(T)[None, :] < np.array([T, max(1, T - 3), T])[:, None]
    pages = np.take_along_axis(tables, pos // page, axis=1)
    cache = attn.write_kv_pages(
        jnp.zeros((P, page, KH * 2 * D), jnp.bfloat16),
        attn.pack_kv(jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)),
        jnp.asarray(pages), jnp.asarray(pos % page), jnp.asarray(valid))

    want = np.zeros((P, page, KH * 2 * D), np.float32)
    for s, t in zip(*np.nonzero(valid)):
        row = want[pages[s, t], pos[s, t] % page]
        for kh in range(KH):
            row[2 * kh * D:(2 * kh + 1) * D] = k[s, t, kh]
            row[(2 * kh + 1) * D:(2 * kh + 2) * D] = v[s, t, kh]
    np.testing.assert_array_equal(np.asarray(cache, np.float32), want)

    kb, vb = attn.split_kv(cache[jnp.asarray(tables)].reshape(S, B * page, -1),
                           D)                            # [S, B·page, KH, D]
    for s, t in zip(*np.nonzero(valid)):
        np.testing.assert_array_equal(np.asarray(kb[s, pos[s, t]], np.float32),
                                      k[s, t])
        np.testing.assert_array_equal(np.asarray(vb[s, pos[s, t]], np.float32),
                                      v[s, t])


@pytest.mark.parametrize("B,T,H,D,chunk", [
    (2, 64, 2, 32, 16), (1, 128, 4, 64, 64), (1, 32, 2, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_vs_oracle(B, T, H, D, chunk, dtype):
    rng = np.random.default_rng(hash((B, T, H, D)) % 2**31)
    r = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype) * 0.5
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype) * 0.5
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype)
    w = jnp.asarray(rng.uniform(0.8, 0.999, size=(B, T, H, D)), dtype)
    u = jnp.asarray(rng.normal(size=(H, D)), dtype) * 0.3
    out_k = rwkv6_chunked_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    out_r = ref.rwkv6_scan_ref(r, k, v, w, u)
    ref_max = float(jnp.max(jnp.abs(out_r.astype(jnp.float32))))
    tol = (1e-4 if dtype == jnp.float32 else 3e-2) * max(ref_max, 1.0)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol)


@pytest.mark.parametrize("E,C,d,ff,tb,fb", [
    (4, 16, 32, 64, 8, 32), (2, 32, 64, 128, 16, 64), (3, 8, 16, 32, 8, 16),
])
def test_fused_moe_vs_oracle(E, C, d, ff, tb, fb):
    rng = np.random.default_rng(hash((E, C, d)) % 2**31)
    x = jnp.asarray(rng.normal(size=(E, C, d)), jnp.float32) * 0.5
    wg = jnp.asarray(rng.normal(size=(E, d, ff)), jnp.float32) * 0.1
    wu = jnp.asarray(rng.normal(size=(E, d, ff)), jnp.float32) * 0.1
    wd = jnp.asarray(rng.normal(size=(E, ff, d)), jnp.float32) * 0.1
    o_k = fused_moe_ffn(x, wg, wu, wd, token_block=tb, ff_block=fb,
                        interpret=True)
    o_r = ref.fused_moe_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


@pytest.mark.parametrize("B,T,di,ds,chunk,cb", [
    (1, 32, 16, 4, 8, 8), (2, 64, 32, 8, 16, 16), (1, 16, 8, 4, 16, 8),
])
def test_mamba_chunked_scan_vs_oracle(B, T, di, ds, chunk, cb):
    from repro.kernels.mamba_scan import mamba_chunked_scan
    rng = np.random.default_rng(hash((B, T, di)) % 2**31)
    dA = jnp.asarray(rng.uniform(0.7, 0.999, (B, T, di, ds)), jnp.float32)
    dBx = jnp.asarray(rng.normal(size=(B, T, di, ds)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, T, ds)), jnp.float32)
    got = mamba_chunked_scan(dA, dBx, C, chunk=chunk, channel_block=cb,
                             interpret=True)
    want = ref.mamba_scan_ref(dA, dBx, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
