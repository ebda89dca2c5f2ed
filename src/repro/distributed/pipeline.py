"""Pipeline-parallel execution: GPipe-style training step and the gLLM
serving tick, both as `shard_map` programs over the derived mesh.

Manual axes: `stage` (+ `data`, + `pod` when present) — activations move by
`lax.ppermute`, MoE tokens by `lax.all_to_all`, data-parallel gradient
reduction happens in the shard_map transpose.  The `tensor` axis stays
auto: GSPMD shards every matmul from the parameter shardings.

The serving tick is the SPMD expression of gLLM's asynchronous runtime: all
stages execute simultaneously on *different* micro-batches; per-tick token
counts are static buckets, so a pipeline bubble is exactly the padding that
Token Throttling minimizes (DESIGN.md §2).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.optimizer import AdamConfig, AdamState, adam_update
from repro.launch.mesh import manual_axes
from repro.models import serve as serve_lib
from repro.models import transformer as tfm
from repro.models.serve import ServeDims


# ----------------------------------------------------------------------------
# Spec plumbing
# ----------------------------------------------------------------------------

def _filter_entry(entry, keep: frozenset):
    if entry is None:
        return None
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a in keep)
        return kept if len(kept) > 1 else (kept[0] if kept else None)
    return entry if entry in keep else None


def manual_spec(spec: P, manual: frozenset) -> P:
    """Strip auto axes from a PartitionSpec (shard_map in_specs may only name
    manual axes; the auto part flows from argument shardings)."""
    return P(*(_filter_entry(e, manual) for e in spec))


def remap_data_axis(spec: P, mesh: Mesh) -> P:
    """In multi-pod meshes, per-replica (serve) arrays shard over
    ('pod','data') wherever single-pod specs say 'data'."""
    if "pod" not in mesh.axis_names:
        return spec

    def f(e):
        if e == "data":
            return ("pod", "data")
        if isinstance(e, tuple) and "data" in e:
            return tuple(a for a in e if a != "data") + ("pod", "data")
        return e

    return P(*(f(e) for e in spec))


def tree_specs(tree_of_specs, mesh: Mesh, *, serve: bool = False):
    """(full NamedShardings for args, manual-only specs for shard_map)."""
    man = manual_axes(mesh)

    def full(s):
        s2 = remap_data_axis(s, mesh) if serve else s
        return NamedSharding(mesh, s2)

    def man_only(s):
        s2 = remap_data_axis(s, mesh) if serve else s
        return manual_spec(s2, man)

    is_spec = lambda x: isinstance(x, P)
    return (jax.tree.map(full, tree_of_specs, is_leaf=is_spec),
            jax.tree.map(man_only, tree_of_specs, is_leaf=is_spec))


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ----------------------------------------------------------------------------
# Training: GPipe schedule + loss + grads + Adam inside ONE shard_map
# ----------------------------------------------------------------------------
#
# The whole step is manual over {stage, data(, pod)} so every cross-device
# reduction is an *explicit* collective under our control:
#   * gradient syncs are f32 psums (mixed-precision correct, and it sidesteps
#     an XLA:CPU AllReducePromotion crash on bf16 shard_map-transpose psums);
#   * the loss is computed with the lm_head vocab-sharded over
#     (stage x tensor): the last stage's hidden is broadcast once in f32 and
#     every stage computes its vocab slice — no S-fold redundant head FLOPs;
#   * this is also where gradient compression hooks in (see
#     repro.distributed.collectives).

def _pipeline_scan(cfg: ArchConfig, weights, h_local, *, enc_width: int = 0):
    """Local GPipe schedule: h_local [M_loc, mb, T, d] -> (out, aux).

    Runs inside the manual region; `weights` leaves are local [R, ...]."""
    S = cfg.plan.pp
    perm = [(i, (i + 1) % S) for i in range(S)]
    M_loc = h_local.shape[0]
    stage = jax.lax.axis_index("stage")
    state = jnp.zeros_like(h_local[0])
    outbuf = jnp.zeros_like(h_local)

    def tick(carry, t):
        st, out, aux = carry
        inp = jax.lax.dynamic_index_in_dim(
            h_local, jnp.clip(t, 0, M_loc - 1), 0, keepdims=False)
        cur = jnp.where(stage == 0, inp, st)
        y, aux_s = tfm.stage_forward_train(cfg, weights, cur,
                                           enc_width=enc_width)
        oidx = jnp.clip(t - (S - 1), 0, M_loc - 1)
        write = (stage == S - 1) & (t >= S - 1)
        prev = jax.lax.dynamic_index_in_dim(out, oidx, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(write, y, prev), oidx, 0)
        real = (t >= stage) & (t < stage + M_loc)   # non-bubble ticks
        aux = aux + jnp.where(real, aux_s, 0.0)
        nxt = jax.lax.ppermute(y, "stage", perm) if S > 1 else y
        return (nxt, out, aux), None

    (_, outbuf, aux), _ = jax.lax.scan(
        tick, (state, outbuf, jnp.zeros((), jnp.float32)),
        jnp.arange(M_loc + S - 1))
    return outbuf, aux


def _sharded_loss(cfg: ArchConfig, params, hid, labels):
    """Cross-entropy with lm_head vocab-sharded over the manual `stage` axis
    (plus auto `tensor`).  hid [M_loc, mb, T, d] is valid on the LAST stage
    only; it is masked+psum-broadcast in f32, then each stage computes its
    vocab slice of the logits.  Returns (sum_nll, sum_mask) local f32."""
    S = cfg.plan.pp
    stage = jax.lax.axis_index("stage")
    fn = params["final_norm"]
    w = params["embed"]["tok"].T if cfg.tie_embeddings \
        else params["lm_head"]["w"]
    V_shard = w.shape[-1]                       # local (stage) vocab slice
    v_off = stage * V_shard

    def loss_mb(hl):
        h_m, lab = hl                           # [mb, T, d], [mb, T]
        if "b" in fn:
            from repro.models.layers import layernorm
            h_m = layernorm(h_m, fn["g"], fn["b"], cfg.norm_eps)
        else:
            from repro.models.layers import rmsnorm
            h_m = rmsnorm(h_m, fn["g"], cfg.norm_eps)
        h32 = jnp.where(stage == S - 1, h_m, 0).astype(jnp.float32)
        h32 = jax.lax.psum(h32, "stage") if S > 1 else h32   # bcast (f32)
        logits = (h32.astype(w.dtype) @ w).astype(jnp.float32)  # [mb,T,Vs]
        m_loc = jnp.max(jax.lax.stop_gradient(logits), axis=-1)
        m = jax.lax.pmax(m_loc, "stage") if S > 1 else m_loc
        m = jax.lax.stop_gradient(m)   # stability shift only; lse grad exact
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        se = jax.lax.psum(se, "stage") if S > 1 else se
        lse = m + jnp.log(se)
        lab_c = jnp.maximum(lab, 0)
        in_shard = (lab_c >= v_off) & (lab_c < v_off + V_shard)
        gold_loc = jnp.take_along_axis(
            logits, jnp.clip(lab_c - v_off, 0, V_shard - 1)[..., None],
            axis=-1)[..., 0]
        gold = jnp.where(in_shard, gold_loc, 0.0)
        gold = jax.lax.psum(gold, "stage") if S > 1 else gold
        mask = (lab >= 0).astype(jnp.float32)
        nll = (lse - gold) * mask
        return jnp.sum(nll), jnp.sum(mask)

    def scan_body(carry, hl):
        n, c = jax.checkpoint(loss_mb)(hl)
        return (carry[0] + n, carry[1] + c), None

    (nll, cnt), _ = jax.lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hid, labels))
    return nll, cnt


def _grad_sync_axes(spec: P, man: frozenset) -> Tuple[str, ...]:
    """A gradient must be psum'd over every manual axis its parameter does
    NOT shard (i.e. axes over which the parameter is replicated)."""
    used = set()
    for e in spec:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                used.add(a)
    return tuple(sorted(man - used))


def build_train_step(cfg: ArchConfig, mesh: Mesh, *,
                     adam: AdamConfig = AdamConfig(),
                     aux_coef: float = 0.01,
                     enc_width: int = 0,
                     grad_compression: Optional[str] = None):
    """Returns step_fn(params, opt_state, batch) -> (params, opt, metrics).

    batch = {tokens [M, mbg, T] int32 (M over `pod`, mbg over `data`),
    labels [M, mbg, T] int32 (-100 = masked), optional
    "embeds" [M, mbg, Tv, d] — the vlm/audio frontend-stub rows}.
    """
    from repro.distributed.collectives import compressed_psum

    man = manual_axes(mesh)
    has_pod = "pod" in mesh.axis_names
    pspecs = tfm.param_pspecs(cfg)
    _, p_man = tree_specs(pspecs, mesh)
    opt_man = AdamState(step=P(), m=p_man, v=p_man)
    tok_spec = P("pod", "data", None) if has_pod else P(None, "data", None)
    emb_spec = P(*(tuple(tok_spec) + (None,)))

    def _make_body(has_embeds: bool):
        def body(params, opt_state, tokens, labels, *rest):
            embeds = rest[0] if has_embeds else None

            def loss_fn(params):
                stages_w = jax.tree.map(lambda a: a[0], params["stages"])
                h = jnp.take(params["embed"]["tok"], tokens, axis=0)
                if embeds is not None:
                    Tv = embeds.shape[2]
                    h = jnp.concatenate([embeds.astype(h.dtype),
                                         h[:, :, Tv:]], axis=2)
                hid, aux = _pipeline_scan(cfg, stages_w, h,
                                          enc_width=enc_width)
                nll, cnt = _sharded_loss(cfg, params, hid, labels)
                dp = tuple(a for a in ("pod", "data") if a in man)
                if dp:
                    nll = jax.lax.psum(nll, dp)
                    cnt = jax.lax.psum(cnt, dp)
                    aux = jax.lax.psum(
                        aux, dp + (("stage",) if cfg.plan.pp > 1 else ()))
                    aux = aux / jax.lax.psum(1, dp)
                loss = nll / jnp.maximum(cnt, 1.0)
                return loss + aux_coef * aux, (loss, aux)

            (total, (loss, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)

            # explicit f32 gradient sync over replicated axes
            def sync(spec, g):
                axes = _grad_sync_axes(spec, man)
                if not axes:
                    return g.astype(jnp.float32)
                return compressed_psum(g, axes, mode=grad_compression)

            grads = jax.tree.map(sync, pspecs, grads,
                                 is_leaf=lambda x: isinstance(x, P))

            # global grad norm: shard-local squares psum'd over the axes that
            # shard each leaf (replicated leaves contribute once)
            def leaf_sq(spec, g):
                used = set()
                for e in spec:
                    for a in (e if isinstance(e, tuple) else (e,)):
                        if a in man:
                            used.add(a)
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                return jax.lax.psum(s, tuple(sorted(used))) if used else s

            gsq = sum(jax.tree.leaves(jax.tree.map(
                leaf_sq, pspecs, grads, is_leaf=lambda x: isinstance(x, P))))
            gnorm = jnp.sqrt(gsq)
            new_params, new_opt, _ = adam_update(adam, grads, params,
                                                 opt_state, gnorm=gnorm)
            metrics = {"loss": loss, "aux": aux, "total": total,
                       "gnorm": gnorm}
            return new_params, new_opt, metrics

        extra = (emb_spec,) if has_embeds else ()
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_man, opt_man, tok_spec, tok_spec) + extra,
            out_specs=(p_man, opt_man, {k: P() for k in
                                        ("loss", "aux", "total", "gnorm")}),
            axis_names=man, check_vma=False)

    fns = {}

    def step(params, opt_state, batch):
        has_embeds = "embeds" in batch
        if has_embeds not in fns:
            fns[has_embeds] = _make_body(has_embeds)
        args = (params, opt_state, batch["tokens"], batch["labels"])
        if has_embeds:
            args += (batch["embeds"],)
        return fns[has_embeds](*args)

    return step


# ----------------------------------------------------------------------------
# Serving: one pipeline tick inside shard_map
# ----------------------------------------------------------------------------

def build_serve_tick(cfg: ArchConfig, mesh: Mesh, dims: ServeDims,
                     *, carry_dims: Optional[ServeDims] = None):
    """Returns (tick_fn, specs) where

    tick_fn(params, caches, carry, meta, fresh) ->
        (new_carry, new_caches, tokens, top_logprobs)

    carry  = {"xp": [S, DSp, W, d], "xd": [S, DSd, 1, d]}
    fresh  = {"xp": [DSp, W, d], "xd": [DSd, 1, d]}  (stage-0 inputs, embedded)
    meta   = stage-stacked ServeMeta dict
    tokens = [D*(Sp+Sd)] int32 sampled ids (greedy), -1 for padding rows
    top_logprobs = [D*(Sp+Sd), 2] f32: each row's two largest log-probs

    **Bucketed programs.**  When `carry_dims` is given (the FULL ladder dims,
    `dims` being a smaller bucket from `bucket_ladder`), the tick accepts and
    returns the full-shape carry but computes only the bucket region: the
    carry is sliced to `[:dims.Sp, :dims.prefill_width]` / `[:dims.Sd]`
    inside the manual region, and the permuted result is written back into
    the same slice, leaving the (never-read) out-of-bucket region untouched.
    Caches, params, and carry buffers are therefore shared — byte-compatible
    and donation-compatible — across every program in the ladder; meta and
    fresh arrive already at bucket shape.
    """
    S = cfg.plan.pp
    man = manual_axes(mesh)
    perm = [(i, (i + 1) % S) for i in range(S)]
    Sp, Sd, W = dims.Sp, dims.Sd, dims.prefill_width
    full = carry_dims or dims
    sliced = (full.Sp, full.prefill_width, full.Sd) != (Sp, W, Sd)

    def body(stage_params, caches, xp, xd, meta, fresh_xp, fresh_xd):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        caches = jax.tree.map(lambda a: a[0], caches)
        meta = {k: v[0] for k, v in meta.items()}
        xp_full, xd_full = xp[0], xd[0]
        if sliced:
            xp = xp_full[:Sp, :W]
            xd = xd_full[:Sd]
        else:
            xp, xd = xp_full, xd_full
        stage = jax.lax.axis_index("stage")

        if Sp:
            xp = jnp.where(stage == 0, fresh_xp, xp)
        if Sd:
            xd = jnp.where(stage == 0, fresh_xd, xd)

        xp2, xd2, new_caches = serve_lib.stage_forward_serve(
            cfg, stage_params, caches, xp, xd, meta, dims)

        # rows whose logits sample a token (outside, on the last stage's out)
        samples = []
        if Sp:
            idx = dims.Te + jnp.maximum(meta["p_chunk_lens"] - 1, 0)
            samples.append(jnp.take_along_axis(
                xp2, idx[:, None, None], axis=1)[:, 0, :])
        if Sd:
            samples.append(xd2[:, 0, :])
        sample_h = jnp.concatenate(samples, axis=0) if len(samples) > 1 \
            else samples[0]

        xp_next = jax.lax.ppermute(xp2, "stage", perm) if Sp else xp2
        xd_next = jax.lax.ppermute(xd2, "stage", perm) if Sd else xd2
        if sliced:
            xp_next = xp_full.at[:Sp, :W].set(xp_next) if Sp else xp_full
            xd_next = xd_full.at[:Sd].set(xd_next) if Sd else xd_full
        return (xp_next[None], xd_next[None],
                jax.tree.map(lambda a: a[None], new_caches),
                sample_h[None])

    # ---- specs.  Weights replicate across pods (EP stays intra-pod); all
    # per-replica runtime state (caches/carries/meta) shards over pod+data.
    pspecs = tfm.param_pspecs(cfg)
    cspecs = serve_lib.cache_pspecs(cfg, dims)
    mspecs = serve_lib.meta_pspecs(dims)
    carry_spec = P("stage", "data", None, None)
    fresh_spec = P("data", None, None)

    w_full, w_man = tree_specs(pspecs["stages"], mesh, serve=False)
    c_full, c_man = tree_specs(cspecs, mesh, serve=True)
    m_full, m_man = tree_specs(mspecs, mesh, serve=True)
    carry_full, carry_man = tree_specs(carry_spec, mesh, serve=True)
    fresh_full, fresh_man = tree_specs(fresh_spec, mesh, serve=True)
    sample_spec = manual_spec(remap_data_axis(P("stage", "data", None), mesh),
                              man)

    inner = jax.shard_map(
        body, mesh=mesh,
        in_specs=(w_man, c_man, carry_man, carry_man, m_man,
                  fresh_man, fresh_man),
        out_specs=(carry_man, carry_man, c_man, sample_spec),
        axis_names=man, check_vma=False)

    def tick(params, caches, carry, meta, fresh, sampling=None):
        """sampling (optional): {"temps": [rows] f32 (0 => greedy),
        "seed": uint32 scalar} — per-request temperature sampling for the
        micro-batch exiting this tick."""
        xp_n, xd_n, caches_n, sample = inner(
            params["stages"], caches, carry["xp"], carry["xd"], meta,
            fresh["xp"], fresh["xd"])
        h_last = sample[-1]                       # [D*(Sp+Sd), d]
        with jax.named_scope("head"):
            logits = tfm.head_apply(cfg, params, h_last).astype(jnp.float32)
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if sampling is not None:
                temps = sampling["temps"].astype(jnp.float32)
                key = jax.random.key(sampling["seed"])
                scaled = logits / jnp.maximum(temps, 1e-3)[:, None]
                drawn = jax.random.categorical(key, scaled, axis=-1) \
                    .astype(jnp.int32)
                tokens = jnp.where(temps > 0.0, drawn, greedy)
            else:
                tokens = greedy
            logprobs = jax.nn.log_softmax(logits, axis=-1)
            top2 = jax.lax.top_k(logprobs, 2)[0]
        return ({"xp": xp_n, "xd": xd_n}, caches_n, tokens, top2)

    specs = {
        "params_stages": (w_full, w_man),
        "caches": (c_full, c_man),
        "meta": (m_full, m_man),
        "carry": (carry_full, carry_man),
        "fresh": (fresh_full, fresh_man),
    }
    return tick, specs
