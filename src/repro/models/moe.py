"""Mixture-of-Experts with explicit expert parallelism.

Dispatch is sort-based with static capacity buffers (GShard-style dropping,
but without the O(T*E*C) one-hot dispatch tensors — at kimi-k2 scale those
would be ~10^11 elements).  With ``ep_axis`` set (kimi, jamba) the experts are
sharded over the `data` mesh axis and tokens move via two `all_to_all`s; each
expert's FFN dims are additionally sharded over `tensor` by GSPMD.  The same
code path (NS=1) serves replicated-expert archs (olmoe) and CPU smoke tests.

Everything is differentiable (sorts only compute indices; gathers/scatters
carry gradients), so the training path reuses it unchanged.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _round8(x: int) -> int:
    return max(8, (x + 7) // 8 * 8)


def _axis_size(axis: Optional[str]) -> int:
    if axis is None:
        return 1
    return jax.lax.psum(1, axis)


def route(x: jax.Array, router_w: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax-then-top-k routing with weight renormalization.

    Returns (weights [T,K] f32, expert_ids [T,K] i32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # GShard load-balancing auxiliary loss: E * mean_e(frac_tokens_e * mean_prob_e)
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    fe = jnp.mean(one_hot_top1, axis=0)
    aux = E * jnp.sum(fe * me)
    return w, idx.astype(jnp.int32), aux


def _group_rows(values: jax.Array, group_ids: jax.Array, num_groups: int,
                capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort rows by group and scatter into [num_groups, capacity, ...] buffers.

    Returns (buffers, order, slot_group, slot_pos); rows beyond capacity drop.
    `group_ids` >= num_groups mark invalid rows (never stored).
    """
    n = group_ids.shape[0]
    order = jnp.argsort(group_ids, stable=True)
    sg = group_ids[order]
    starts = jnp.searchsorted(sg, jnp.arange(num_groups))
    pos = jnp.arange(n) - starts[jnp.minimum(sg, num_groups - 1)]
    pos = jnp.where(sg < num_groups, pos, capacity)      # invalid -> dropped
    buf = jnp.zeros((num_groups, capacity) + values.shape[1:], values.dtype)
    buf = buf.at[sg, pos].set(values[order], mode="drop")
    return buf, order, sg, pos


def _ungroup_rows(buffers: jax.Array, order: jax.Array, slot_group: jax.Array,
                  slot_pos: jax.Array) -> jax.Array:
    """Inverse of `_group_rows`: read each row's result back (dropped -> 0)."""
    n = order.shape[0]
    capacity = buffers.shape[1]
    ok = slot_pos < capacity
    vals = buffers[jnp.minimum(slot_group, buffers.shape[0] - 1),
                   jnp.minimum(slot_pos, capacity - 1)]
    vals = jnp.where(ok[(...,) + (None,) * (vals.ndim - 1)], vals, 0)
    out = jnp.zeros((n,) + buffers.shape[2:], buffers.dtype)
    return out.at[order].set(vals)


def expert_ffn(xb: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    """Grouped SwiGLU over padded per-expert buffers.

    xb [E_loc, C, d]; weights [E_loc, d, ff] / [E_loc, ff, d].  On TPU,
    dispatches to the fused Pallas kernel (expert hidden never leaves VMEM);
    elsewhere the batched einsum is the XLA-fused grouped GEMM (GSPMD shards
    `ff` over `tensor`)."""
    from repro.kernels import ops as kops
    if kops.on_tpu() and xb.shape[1] % 8 == 0 \
            and w_gate.shape[-1] % 128 == 0:
        from repro.kernels.moe_gemm import fused_moe_ffn
        return fused_moe_ffn(xb, w_gate, w_up, w_down)
    h = jnp.einsum("ecd,edf->ecf", xb, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xb, w_up)
    h = jax.nn.silu(h) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_apply(
    x: jax.Array,                     # [T, d] flattened tokens
    params: Dict[str, jax.Array],
    *,
    top_k: int,
    ep_axis: Optional[str] = None,
    capacity_factor: float = 1.25,
    row_valid: Optional[jax.Array] = None,   # [T] bool: padding rows opt out
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [T, d], aux_loss).  `params` holds:
    router [d, E]; w_gate/w_up [E_loc, d, ff]; w_down [E_loc, ff, d];
    optional shared-expert s_gate/s_up [d, ffs], s_down [ffs, d].
    E_loc == E / axis_size(ep_axis).  Rows with row_valid=False (static-tick
    bucket padding) are routed nowhere and consume no expert capacity."""
    import os
    capacity_factor = float(os.environ.get("REPRO_MOE_CF", capacity_factor))
    T, d = x.shape
    E_loc = params["w_gate"].shape[0]
    NS = _axis_size(ep_axis)
    E = E_loc * NS

    w, idx, aux = route(x, params["router"], top_k)
    N = T * top_k
    flat_e = idx.reshape(N)
    flat_w = w.reshape(N)
    src = jnp.repeat(jnp.arange(T), top_k)
    if row_valid is not None:
        flat_e = jnp.where(row_valid[src], flat_e, E)     # invalid sentinel
    xs = x[src]                                           # [N, d]

    if NS > 1:
        # ---- EP: bucket by destination shard, all_to_all, compute, return
        cap_send = _round8(int(N / NS * capacity_factor) + 1)
        dest = flat_e // E_loc
        payload = jnp.concatenate(
            [xs, (flat_e % E_loc).astype(x.dtype)[:, None]], axis=-1)
        buf, order, sg, pos = _group_rows(payload, dest, NS, cap_send)
        valid = jnp.zeros((NS, cap_send, 1), x.dtype).at[sg, pos].set(
            jnp.ones((N, 1), x.dtype), mode="drop")
        buf = jnp.concatenate([buf, valid], axis=-1)
        rbuf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        rx = rbuf[..., :d].reshape(NS * cap_send, d)
        re = rbuf[..., d].reshape(NS * cap_send).astype(jnp.int32)
        rvalid = rbuf[..., d + 1].reshape(NS * cap_send) > 0.5
        re = jnp.where(rvalid, re, E_loc)                 # invalid -> dropped
        cap_e = _round8(int(NS * cap_send / E_loc * capacity_factor) + 1)
        ebuf, order2, sg2, pos2 = _group_rows(rx, re, E_loc, cap_e)
        y = expert_ffn(ebuf, params["w_gate"], params["w_up"], params["w_down"])
        ry = _ungroup_rows(y, order2, sg2, pos2)          # [NS*cap_send, d]
        ry = ry.reshape(NS, cap_send, d)
        yback = jax.lax.all_to_all(ry, ep_axis, split_axis=0, concat_axis=0,
                                   tiled=True)
        ys = _ungroup_rows(yback, order, sg, pos)         # [N, d]
    else:
        cap_e = _round8(int(N / E * capacity_factor) + 1)
        ebuf, order2, sg2, pos2 = _group_rows(xs, flat_e, E, cap_e)
        y = expert_ffn(ebuf, params["w_gate"], params["w_up"], params["w_down"])
        ys = _ungroup_rows(y, order2, sg2, pos2)          # [N, d]

    out = jnp.zeros((T, d), jnp.float32)
    out = out.at[src].add(flat_w[:, None] * ys.astype(jnp.float32))

    if "s_gate" in params:
        shared = (jax.nn.silu(x @ params["s_gate"]) * (x @ params["s_up"])) \
            @ params["s_down"]
        out = out + shared.astype(jnp.float32)
    return out.astype(x.dtype), aux


def moe_ref(x: jax.Array, params: Dict[str, jax.Array], *, top_k: int) -> jax.Array:
    """Dense per-expert oracle (no capacity drops) for correctness tests."""
    w, idx, _ = route(x, params["router"], top_k)
    E = params["router"].shape[-1]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(E):
        ye = (jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])) \
            @ params["w_down"][e]
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        out = out + gate[:, None] * ye.astype(jnp.float32)
    if "s_gate" in params:
        out = out + ((jax.nn.silu(x @ params["s_gate"]) * (x @ params["s_up"]))
                     @ params["s_down"]).astype(jnp.float32)
    return out.astype(x.dtype)
