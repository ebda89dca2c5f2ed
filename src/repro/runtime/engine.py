"""gLLM serving engine: the asynchronous pipeline runtime (paper §3.3)
adapted to JAX.

Roles (paper -> here):
  * driver worker   -> the shared `TickLoop` (runtime/core.py): owns the
    schedule→execute→complete cycle and the depth-S micro-batch ring.
  * ordinary worker -> `JaxBackend`: the SPMD serving tick
    (`build_serve_tick`); each mesh `stage` shard executes its resident
    micro-batch; activations move by collective-permute (the NCCL path),
    metadata is computed host-side one tick ahead (the ZeroMQ dual-phase
    path) and overlaps device compute because jit dispatch is asynchronous.
  * frontend        -> `repro.serving.LLMServer` (streams on the asyncio
    loop or an HTTP handler thread while a worker thread ticks) and the
    HTTP process around it (`repro.serving.http`): decoupled request
    intake / token streaming.

`PipelineEngine` is the user-facing handle binding scheduler + KV + backend
+ loop; it is exact (it runs the real model) and is used by the examples,
integration tests, and the output-equivalence benchmark.  Scale experiments
run the *same* TickLoop over the calibrated roofline `SimBackend` instead
(runtime/simulator.py).
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import (
    PagedKVManager,
    PipelineScheduler,
    Request,
    SamplingParams,
    ScheduledBatch,
    ThrottleConfig,
)
from repro.models import serve as serve_lib
from repro.models import transformer as tfm
from repro.models.serve import ServeDims
from repro.runtime.core import ExecResult, ExecutionBackend, Phases, TickLoop


class SlotAllocator:
    """Sequence slots for recurrent state / encoder caches."""

    def __init__(self, n: int) -> None:
        self.free = list(range(n - 1, -1, -1))
        self.owner: Dict[str, int] = {}

    def get(self, request_id: str) -> int:
        if request_id in self.owner:
            return self.owner[request_id]
        if not self.free:
            raise MemoryError("out of state slots")
        s = self.free.pop()
        self.owner[request_id] = s
        return s

    def release(self, request_id: str) -> None:
        s = self.owner.pop(request_id, None)
        if s is not None:
            self.free.append(s)


@dataclass
class EngineStats:
    ticks: int = 0
    tokens_out: int = 0
    padded_prefill: int = 0     # bucket padding = TPU pipeline bubbles
    padded_decode: int = 0
    scheduled_prefill: int = 0
    scheduled_decode: int = 0
    scanned_pages: int = 0      # KV pages the attention scan walks per tick
    live_pages: int = 0         # KV pages actually holding context
    host_s: float = 0.0         # host-side per-tick work (meta/fresh/dispatch)
    last_bucket: Optional[Dict[str, int]] = None  # selected serve shape
    # host seconds per phase of `execute` (tick.stack, tick.embed,
    # tick.sampling, tick.dispatch) and per wait on the device
    # (tick.embed_wait inside tick.embed; tick.readback_wait, the exiting
    # batch's token readback, inside the loop's tick.retire)
    phases: Phases = field(default_factory=Phases)


class JaxBackend(ExecutionBackend):
    """ExecutionBackend running the exact jitted SPMD serve tick.

    Owns everything device-side: params, paged KV tensors, recurrent-state
    caches, the inter-stage activation carry, and the per-request host state
    (state slots, encoder embeddings).  `prepare` builds the tick metadata at
    schedule time; `execute` stacks the ring's metadata, dispatches the tick,
    and returns a *deferred* `ExecResult` — the blocking readback of the
    exiting micro-batch's tokens lives in its `pending` thunk, so a sync
    TickLoop forces it immediately while the async loop lets it overlap the
    next tick's host work (DESIGN.md §12).

    With `bucketed=True` the backend compiles the fixed `bucket_ladder`
    of serve shapes (all sharing the full-dims caches and carry) and each
    tick runs in the smallest bucket covering every micro-batch in the
    ring; `warm_start()` compiles the whole ladder up front so steady
    state never recompiles (`compile_count()` exposes the jit cache sizes
    for the zero-recompile assertion).
    """

    def __init__(self, cfg: ArchConfig, dims: ServeDims, params, mesh,
                 kv: PagedKVManager, *, dtype=None,
                 bucketed: bool = False) -> None:
        from repro.distributed.pipeline import build_serve_tick

        self.cfg = cfg
        self.dims = dims
        self.mesh = mesh
        self.params = params
        self.dtype = dtype or jnp.dtype(cfg.dtype)
        self.kv = kv
        self.slots = SlotAllocator(dims.slots)
        self.enc_embeds: Dict[str, np.ndarray] = {}
        self.stats = EngineStats()
        self.bucketed = bucketed
        self.ladder: Tuple[ServeDims, ...] = (
            serve_lib.bucket_ladder(dims) if bucketed else (dims,))
        self._build_serve_tick = build_serve_tick
        self._ticks: Dict[Tuple[int, int, int, int, int], Any] = {}

        def embed(p, t):
            with jax.named_scope("embed"):
                return jnp.take(p["embed"]["tok"], t, axis=0)
        self._embed = jax.jit(embed)
        # Every tick input is placed with an explicit sharding on the
        # engine's mesh, so its type is the same whichever mesh context the
        # caller is in (inside a `jax.set_mesh` block or outside any) and
        # the jit cache sees one signature per bucket.
        self._meta_sh = self._sharding(P("stage", "data"))
        self._fresh_sh = self._sharding(P("data", None, None))
        self._repl_sh = self._sharding(P())
        carry_sh = self._sharding(P("stage", "data", None, None))
        S, W = cfg.plan.pp, dims.prefill_width
        self.caches = jax.tree.map(
            lambda a, spec: jnp.zeros(a.shape, a.dtype,
                                      device=self._sharding(spec)),
            serve_lib.abstract_caches(cfg, dims, self.dtype),
            serve_lib.cache_pspecs(cfg, dims),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        self.carry = {
            "xp": jnp.zeros((S, dims.Sp, W, cfg.d_model), self.dtype,
                            device=carry_sh),
            "xd": jnp.zeros((S, dims.Sd, 1, cfg.d_model), self.dtype,
                            device=carry_sh),
        }
        self._seed = 0
        self._prep_s = 0.0          # host prepare() time since last execute
        self._zero_meta_np()        # build the template now: one-time jnp
        #                             dispatch must not bill the first tick

    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ------------------------------------------------------- bucket programs
    def _get_tick(self, bucket: ServeDims):
        key = (bucket.Sp, bucket.C, bucket.Sd, bucket.Bp, bucket.Bd)
        fn = self._ticks.get(key)
        if fn is None:
            carry_dims = self.dims if bucket != self.dims else None
            tick, _ = self._build_serve_tick(self.cfg, self.mesh, bucket,
                                             carry_dims=carry_dims)
            fn = jax.jit(tick, donate_argnums=(1, 2))
            self._ticks[key] = fn
        return fn

    def compile_count(self) -> int:
        """Total jit-compiled signatures across the bucket programs (the
        zero-recompile-in-steady-state assertion reads this)."""
        total = 0
        for fn in self._ticks.values():
            if hasattr(fn, "_cache_size"):
                total += fn._cache_size()
        return total

    def warm_start(self) -> None:
        """Compile every ladder program with a bubble tick (zero metadata —
        a state no-op, like any pipeline bubble) before serving begins.

        The ladder's first program runs once more at the end: its first call
        took the freshly-allocated caches/carry, whose shardings differ from
        the donated program outputs every steady-state call receives, so it
        alone needs its steady-state signature compiled separately.  After
        warm_start no serving tick compiles (``compile_count()`` is flat).
        """
        def bubble(bucket: ServeDims) -> None:
            # same mesh context as execute(): the jit cache keys on the
            # ambient mesh, so warming under a different context would
            # compile signatures serving never hits
            with jax.set_mesh(self.mesh):
                self.carry, self.caches, tokens, _ = self._get_tick(bucket)(
                    self.params, self.caches, self.carry,
                    *self._bubble_inputs(bucket))
            np.asarray(tokens)      # block: compile + execute now, not later

        for bucket in self.ladder:
            bubble(bucket)
        bubble(self.ladder[0])

    def _bubble_inputs(self, bucket: ServeDims) -> tuple:
        """(meta, fresh, sampling) of a bubble tick: zero metadata, a
        state no-op like any pipeline bubble."""
        ring = tuple((None, self._zero_meta_np()) for _ in range(self.depth))
        return (self._stack_meta(ring, bucket),
                self._build_fresh(None, bucket),
                self._put_sampling(
                    np.zeros(bucket.Sp + bucket.Sd, np.float32), 0))

    def lower_tick(self, bucket: Optional[ServeDims] = None):
        """The `jax.stages.Lowered` tick program of `bucket` (default: the
        full dims) on the engine's current state — for inspecting what the
        compiler makes of it (`.compile().as_text()`, memory analysis)."""
        bucket = bucket or self.dims
        with jax.set_mesh(self.mesh):
            return self._get_tick(bucket).lower(
                self.params, self.caches, self.carry,
                *self._bubble_inputs(bucket))

    def _select_bucket(self, ring: Sequence[Tuple[Optional[int], Any]]
                       ) -> ServeDims:
        if not self.bucketed:
            return self.dims
        need_c = need_d = need_bp = need_bd = 0
        page = self.dims.page
        for _, m in ring:
            if m["p_chunk_lens"].size:
                need_c = max(need_c, int(m["p_chunk_lens"].max()))
                # block-table depth demand = ring-wide max pages-in-use;
                # context_lens is 0 on empty rows so the max is safe
                need_bp = max(need_bp,
                              -(-int(m["p_context_lens"].max()) // page))
            if m["d_valid"].size:
                need_d = max(need_d, int(np.count_nonzero(m["d_valid"])))
                need_bd = max(need_bd,
                              -(-int(m["d_context_lens"].max()) // page))
        return serve_lib.select_bucket(self.ladder, need_c, need_d,
                                       need_bp=need_bp, need_bd=need_bd)

    @staticmethod
    def _slice_meta_field(key: str, arr: np.ndarray,
                          bucket: ServeDims) -> np.ndarray:
        """Cut one stage-stacked full-dims meta field down to bucket shape."""
        if key.startswith("p_"):
            arr = arr[:, :bucket.Sp]
            if key in ("p_positions", "p_slot_pages", "p_slot_offsets"):
                arr = arr[:, :, :bucket.C]
            elif key == "p_block_tables":
                # depth bucket: the selector guarantees every live page index
                # sits below bucket.Bp, so the tail is always zero padding
                arr = arr[:, :, :bucket.Bp]
        else:
            arr = arr[:, :bucket.Sd]
            if key == "d_block_tables":
                arr = arr[:, :, :bucket.Bd]
        return arr

    def _stack_meta(self, ring: Sequence[Tuple[Optional[int], Any]],
                    bucket: ServeDims) -> dict:
        full = bucket == self.dims
        out = {}
        for k in self._zero_meta_np():
            stacked = np.stack([m[1][k] for m in ring], axis=0)
            if not full:
                stacked = np.ascontiguousarray(
                    self._slice_meta_field(k, stacked, bucket))
            out[k] = jax.device_put(stacked, self._meta_sh)
        return out

    # --------------------------------------------------------------- protocol
    @property
    def depth(self) -> int:
        return self.cfg.plan.pp

    def clock(self) -> float:
        return time.monotonic()

    def prepare(self, batch: Optional[ScheduledBatch]) -> dict:
        t0 = time.perf_counter()
        out = self._zero_meta_np() if batch is None else self._build_meta(batch)
        self._prep_s += time.perf_counter() - t0
        return out

    def execute(self, ring: Sequence[Tuple[Optional[int], Any]],
                exiting_id: Optional[int], now: float) -> ExecResult:
        t0 = time.perf_counter()
        phases = self.stats.phases
        with phases.span("tick.stack"):
            bucket = self._select_bucket(ring)
            meta_dev = self._stack_meta(ring, bucket)
        with phases.span("tick.embed"):
            entering = (self.scheduler.get_batch(ring[0][0])
                        if ring[0][0] is not None else None)
            fresh = self._build_fresh(entering, bucket)
        with phases.span("tick.sampling"):
            sampling = self._build_sampling(exiting_id, bucket)
        with phases.span("tick.dispatch"), jax.set_mesh(self.mesh):
            self.carry, self.caches, tokens, top_lp = self._get_tick(bucket)(
                self.params, self.caches, self.carry, meta_dev, fresh,
                sampling)

        n_p = entering.num_prefill_tokens if entering is not None else 0
        n_d = entering.num_decode_tokens if entering is not None else 0
        self.stats.ticks += 1
        self.stats.scheduled_prefill += n_p
        self.stats.scheduled_decode += n_d
        self.stats.padded_prefill += bucket.Sp * bucket.C - n_p
        self.stats.padded_decode += bucket.Sd - n_d
        # attention-depth accounting (same entering-batch convention as the
        # padded_* counters): what the bucket scans vs. what holds context
        self.stats.scanned_pages += bucket.Sp * bucket.Bp + bucket.Sd * bucket.Bd
        if entering is not None:
            page = self.dims.page
            live = sum(-(-(seq.start_pos + seq.num_tokens) // page)
                       for seq in entering.prefill)
            live += sum(-(-(seq.start_pos + 1) // page)
                        for seq in entering.decode)
            self.stats.live_pages += live
        self.stats.last_bucket = {"Sp": bucket.Sp, "C": bucket.C,
                                  "Sd": bucket.Sd, "Bp": bucket.Bp,
                                  "Bd": bucket.Bd}
        # host_s: everything this tick spent off-device — the prepare()
        # calls since the last execute plus the stack/embed/dispatch above
        host_s = self._prep_s + (time.perf_counter() - t0)
        self._prep_s = 0.0
        self.stats.host_s += host_s

        exiting = (self.scheduler.get_batch(exiting_id)
                   if exiting_id is not None else None)
        if exiting is None:
            return ExecResult(completed_at=now, host_s=host_s)

        prefill_rows = [i for i, seq in enumerate(exiting.prefill)
                        if seq.produces_token]
        n_decode = len(exiting.decode)
        d_off = bucket.Sp

        def readback() -> List[int]:
            with phases.span("tick.readback_wait"):
                host = np.asarray(tokens)   # blocks until the tick finishes
                lps = np.asarray(top_lp)
            rows = prefill_rows + [d_off + j for j in range(n_decode)]
            reqs = [exiting.prefill[i].request for i in prefill_rows]
            reqs += [seq.request for seq in exiting.decode]
            for req, r in zip(reqs, rows):
                # aligned with output_token_ids once the scheduler records
                # this row's token (a discarded token's entry is overwritten)
                del req.output_logprobs[req.num_output_tokens:]
                req.output_logprobs.append((float(lps[r, 0]),
                                            float(lps[r, 1])))
            toks = [int(host[r]) for r in rows]
            self.stats.tokens_out += len(toks)
            return toks

        def probe() -> bool:
            # non-blocking: lets the async loop retire this batch the moment
            # the device is done instead of a fixed tick later
            try:
                return bool(tokens.is_ready())
            except AttributeError:
                return False

        return ExecResult(completed_at=now, host_s=host_s, pending=readback,
                          ready=probe)

    def finish_request(self, req: Request) -> None:
        self.slots.release(req.request_id)
        self.enc_embeds.pop(req.request_id, None)

    def release_resident_state(self, req: Request) -> None:
        """Preemption/abort recovery: the request lost residency, so its
        state slot can be reassigned (recompute rebuilds recurrent state from
        scratch).  Encoder embeddings are kept — recompute needs them."""
        self.slots.release(req.request_id)

    # ------------------------------------------------- live migration (§9)
    # Paged "kv" cache leaves are (stage, repeat, pages, page, ...): one
    # fancy-indexed gather/scatter on the (page, slot) axes moves a request's
    # whole context across every stage and layer.  Slot-indexed leaves
    # (recurrent conv/ssm/wkv state, encoder hidden caches) move by state
    # slot.  On one host this is an array copy; across hosts the same
    # payloads are what would go over the interconnect.

    _SLOT_LEAF_AXIS = {"conv": 2, "ssm": 2, "tm_x": 2, "cm_x": 2, "wkv": 2,
                       "h": 1}

    def export_kv_pages(self, request_id: str,
                        slots: Sequence[Tuple[int, int]]) -> dict:
        pg = jnp.asarray([p for p, _ in slots], jnp.int32)
        off = jnp.asarray([o for _, o in slots], jnp.int32)
        payload = {}
        for gk, grp in self.caches.items():
            for name, arr in grp.items():
                if name == "kv":
                    payload[f"{gk}/{name}"] = arr[:, :, pg, off]
        return payload

    def import_kv_pages(self, request_id: str, payload: dict,
                        slots: Sequence[Tuple[int, int]]) -> None:
        if payload is None:
            return
        pg = jnp.asarray([p for p, _ in slots], jnp.int32)
        off = jnp.asarray([o for _, o in slots], jnp.int32)
        for gk, grp in self.caches.items():
            for name, arr in grp.items():
                if name == "kv":
                    vals = jnp.asarray(payload[f"{gk}/{name}"], arr.dtype)
                    grp[name] = arr.at[:, :, pg, off].set(vals)

    def export_request_state(self, req: Request) -> dict:
        state: Dict[str, Any] = {"enc": self.enc_embeds.pop(req.request_id,
                                                            None),
                                 "slot_leaves": {}}
        s = self.slots.owner.get(req.request_id)
        if s is not None:
            for gk, grp in self.caches.items():
                for name, arr in grp.items():
                    ax = self._SLOT_LEAF_AXIS.get(name)
                    if ax is not None:
                        state["slot_leaves"][f"{gk}/{name}"] = \
                            jnp.take(arr, s, axis=ax)
            self.slots.release(req.request_id)
        return state

    def import_request_state(self, req: Request, state: Optional[dict],
                             resident: bool = True) -> None:
        if state is None:
            return
        if state.get("enc") is not None:
            self.enc_embeds[req.request_id] = state["enc"]
        # residency-scoped state: a non-resident arrival recomputes from
        # scratch, so scattering stale recurrent state (and burning a slot)
        # would only be overwritten
        leaves = state.get("slot_leaves") or {} if resident else {}
        if not leaves:
            return
        s = self.slots.get(req.request_id)
        for gk, grp in self.caches.items():
            for name, arr in grp.items():
                key = f"{gk}/{name}"
                if key in leaves:
                    idx = [slice(None)] * arr.ndim
                    idx[self._SLOT_LEAF_AXIS[name]] = s
                    grp[name] = arr.at[tuple(idx)].set(
                        jnp.asarray(leaves[key], arr.dtype))

    # -------------------------------------------------------------- internals
    def _build_sampling(self, exiting_id, dims: Optional[ServeDims] = None):
        """Per-row temperatures for the micro-batch exiting this tick."""
        dims = dims or self.dims
        rows = dims.Sp + dims.Sd
        temps = np.zeros(rows, np.float32)
        batch = (self.scheduler.get_batch(exiting_id)
                 if exiting_id is not None else None)
        if batch is not None:
            for i, seq in enumerate(batch.prefill):
                temps[i] = seq.request.sampling.temperature
            for j, seq in enumerate(batch.decode):
                temps[dims.Sp + j] = seq.request.sampling.temperature
        self._seed = (self._seed + 1) % (2**31)
        return self._put_sampling(temps, self._seed)

    def _put_sampling(self, temps: np.ndarray, seed: int) -> dict:
        return {"temps": jax.device_put(temps, self._repl_sh),
                "seed": jax.device_put(np.uint32(seed), self._repl_sh)}

    def _zero_meta_np(self) -> dict:
        if not hasattr(self, "_zm"):
            self._zm = {k: np.asarray(v)
                        for k, v in serve_lib.zero_meta(self.dims).items()}
        return self._zm

    def _build_meta(self, batch: ScheduledBatch) -> dict:
        dims = self.dims
        zm = self._zero_meta_np()
        # copy-on-write off the cached zero template: a field is copied the
        # first time the batch writes it, untouched fields alias the shared
        # template (safe — consumers only read; `_stack_meta` copies via
        # np.stack).  A decode-only batch never materializes the p_* fields.
        m = dict(zm)

        def w(k: str) -> np.ndarray:
            if m[k] is zm[k]:
                m[k] = zm[k].copy()
            return m[k]

        for s, seq in enumerate(batch.prefill):
            req = seq.request
            L = seq.num_tokens
            w("p_positions")[s, :L] = seq.start_pos + np.arange(L)
            w("p_chunk_lens")[s] = L
            w("p_context_lens")[s] = seq.start_pos + L
            table = self.kv.block_table(req.request_id)[: dims.Bp]
            w("p_block_tables")[s, : len(table)] = table
            pages = [p for p, _ in seq.slots]
            offs = [o for _, o in seq.slots]
            w("p_slot_pages")[s, :L] = pages
            w("p_slot_offsets")[s, :L] = offs
            w("p_state_slots")[s] = self.slots.get(req.request_id)
            w("p_sample")[s] = int(seq.produces_token)
        for s, seq in enumerate(batch.decode):
            req = seq.request
            w("d_positions")[s] = seq.start_pos
            w("d_context_lens")[s] = seq.start_pos + 1
            table = self.kv.block_table(req.request_id)[: dims.Bd]
            w("d_block_tables")[s, : len(table)] = table
            w("d_slot_pages")[s] = seq.slots[0][0]
            w("d_slot_offsets")[s] = seq.slots[0][1]
            w("d_state_slots")[s] = self.slots.get(req.request_id)
            w("d_valid")[s] = 1
        return m

    def _build_fresh(self, batch: Optional[ScheduledBatch],
                     dims: Optional[ServeDims] = None) -> dict:
        dims, cfg = dims or self.dims, self.cfg
        prefill = batch.prefill if batch is not None else []
        decode = batch.decode if batch is not None else []
        W = dims.prefill_width
        full = self.dims
        xp = np.zeros((max(dims.Sp, 0), W, cfg.d_model), np.float32)
        xd = np.zeros((dims.Sd, 1, cfg.d_model), np.float32)
        # token buffers stay at FULL dims even for smaller buckets, so the
        # embed jit keeps one signature across the whole ladder (warmed at
        # startup) instead of compiling per chunk width mid-serve
        p_tok = np.zeros((max(full.Sp, 1), max(full.C, 1)), np.int32)
        d_tok = np.zeros((max(full.Sd, 1), 1), np.int32)
        for s, seq in enumerate(prefill):
            toks = seq.request.effective_prompt[
                seq.start_pos : seq.start_pos + seq.num_tokens]
            p_tok[s, : len(toks)] = toks
        for s, seq in enumerate(decode):
            d_tok[s, 0] = seq.request.effective_prompt[seq.start_pos]
        # the embed jit keys on the ambient mesh context like any other
        # program: run it under the same scope as the tick call so the
        # warm-time and serve-time signatures coincide.  The lookups queue
        # behind the tick in flight, so reading them back is the host's
        # wait on the device (tick.embed_wait)
        wait = self.stats.phases.span
        if dims.Sp:
            with jax.set_mesh(self.mesh), wait("tick.embed_wait"):
                emb = np.asarray(self._embed(
                    self.params, jax.device_put(p_tok, self._repl_sh)),
                    np.float32)
            emb = emb[: dims.Sp, : max(dims.C, 1)]
            xp[:, dims.Te : dims.Te + emb.shape[1], :] = emb
            for s, seq in enumerate(prefill):
                enc = self.enc_embeds.get(seq.request.request_id)
                if enc is not None:
                    xp[s, : enc.shape[0], :] = enc
        if dims.Sd:
            with jax.set_mesh(self.mesh), wait("tick.embed_wait"):
                xd[:, 0, :] = np.asarray(
                    self._embed(self.params,
                                jax.device_put(d_tok, self._repl_sh)),
                    np.float32)[: dims.Sd, 0, :]
        return {"xp": jax.device_put(xp.astype(self.dtype), self._fresh_sh),
                "xd": jax.device_put(xd.astype(self.dtype), self._fresh_sh)}


class PipelineEngine:
    """Single-process engine (mesh may be 1 device for CPU runs — the SPMD
    tick is identical; only the mesh size changes).  Binds scheduler + KV +
    `JaxBackend` under the shared `TickLoop`."""

    def __init__(
        self,
        cfg: ArchConfig,
        dims: ServeDims,
        params,
        mesh,
        throttle: ThrottleConfig,
        *,
        num_pages: Optional[int] = None,
        dtype=None,
        trace_path: Optional[str] = None,
        async_dispatch: bool = False,
        bucketed: bool = False,
        enable_prefix_caching: bool = False,
    ) -> None:
        if trace_path is not None and async_dispatch:
            # the recorder writes each tick's exit tokens at execute time;
            # a deferred retire would interleave records out of order and
            # break strict replay, so traced engines stay synchronous
            raise ValueError("async_dispatch is incompatible with trace_path "
                             "(traces require synchronous retirement)")
        self.cfg = cfg
        self.dims = dims
        self.mesh = mesh
        self.params = params
        self.kv = PagedKVManager(num_pages or dims.pages, dims.page,
                                 enable_prefix_caching=enable_prefix_caching)
        self.scheduler = PipelineScheduler(
            throttle, self.kv,
            max_model_len=dims.page * max(dims.Bp, dims.Bd),
            max_prefill_seqs=max(dims.Sp, 0),
            max_chunk_tokens=max(dims.C, 1),
            max_decode_seqs=dims.Sd)
        self.backend = JaxBackend(cfg, dims, params, mesh, self.kv,
                                  dtype=dtype, bucketed=bucketed)
        if bucketed:
            self.backend.warm_start()
        # with --trace-out, every tick of the live engine is logged to a
        # replayable JSONL trace (runtime/trace.py); the recorder is a
        # transparent shim around the backend.  The serving layer submits
        # from client threads while a worker thread ticks, so traced
        # engines serialize intake against the tick — otherwise a request's
        # `req` record could land after the tick that batched it and strict
        # replay of our own output would diverge.  Untraced engines keep the
        # lock-free path.
        self.recorder = None
        self._trace_lock = None
        loop_backend = self.backend
        if trace_path is not None:
            import threading

            from repro.runtime.trace import TraceRecorder
            self.recorder = TraceRecorder(self.backend, trace_path)
            self._trace_lock = threading.Lock()
            loop_backend = self.recorder
        self.loop = TickLoop(self.scheduler, loop_backend,
                             async_dispatch=async_dispatch)
        # state slots are tied to residency: free them when the scheduler
        # evicts a request (preemption or batch abort), not only on finish
        self.scheduler.on_preempt = self.backend.release_resident_state
        self._now_fn: Callable[[], float] = time.monotonic

    # ----------------------------------------------------- delegated surfaces
    @property
    def slots(self) -> SlotAllocator:
        return self.backend.slots

    @property
    def enc_embeds(self) -> Dict[str, np.ndarray]:
        return self.backend.enc_embeds

    @property
    def stats(self) -> EngineStats:
        return self.backend.stats

    @property
    def finished(self) -> List[Request]:
        return self.loop.finished

    @property
    def on_token(self) -> Optional[Callable[[Request, int], None]]:
        return self.loop.on_token

    @on_token.setter
    def on_token(self, fn: Optional[Callable[[Request, int], None]]) -> None:
        # streaming hook: called as on_token(request, token_id) per new token
        self.loop.on_token = fn

    # ------------------------------------------------------------------ API
    # process-wide: ids must stay unique across router replicas (the
    # frontend keys token streams by request id)
    _req_counter = itertools.count()

    def add_request(self, prompt: Sequence[int],
                    sampling: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    enc_embeds: Optional[np.ndarray] = None) -> Request:
        rid = request_id or f"req-{next(PipelineEngine._req_counter)}"
        req = Request(rid, list(prompt), sampling or SamplingParams())
        req.metrics.arrival_time = self._now_fn()
        if self.cfg.is_encoder_decoder:
            Te, d = self.dims.Te, self.cfg.d_model
            if enc_embeds is None:
                enc_embeds = np.zeros((Te, d), np.float32)
            self.enc_embeds[rid] = np.asarray(enc_embeds, np.float32)[:Te]
        if self._trace_lock is None:
            self.scheduler.add_request(req)
        else:
            with self._trace_lock:
                self.scheduler.add_request(req)
                self.recorder.record_arrival(req)
        return req

    def abort_request(self, request_id: str) -> bool:
        """User abort: frees KV pages and the state slot / encoder cache.
        In-flight requests finalize when their micro-batch retires (the
        TickLoop's normal release path); returns False when unknown."""
        now = self._now_fn()
        if self._trace_lock is None:
            req = self.scheduler.abort_request(request_id, now)
            if req is None:
                return False
        else:
            with self._trace_lock:
                req = self.scheduler.abort_request(request_id, now)
                if req is None:
                    return False
                self.recorder.record_abort(request_id, now)
        if req.is_finished:
            # immediately finalized (waiting / running): the TickLoop will
            # never retire it, so release backend state and surface it here
            self.backend.finish_request(req)
            self.loop.finished.append(req)
        return True

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    @property
    def busy(self) -> bool:
        return self.loop.busy

    def _ring_busy(self) -> bool:   # back-compat alias
        return self.loop.busy

    # ----------------------------------------------------------------- tick
    def step(self) -> List[Request]:
        """One pipeline tick.  Returns requests finishing this tick."""
        if self._trace_lock is None:
            return self.loop.step(self._now_fn())
        with self._trace_lock:
            return self.loop.step(self._now_fn())

    def drain(self, max_ticks: int = 100000) -> List[Request]:
        if self._trace_lock is None:
            return self.loop.drain(self._now_fn, max_ticks)
        out: List[Request] = []
        for _ in range(max_ticks):          # lock per tick, not per drain
            # the no-work check and the step share ONE lock acquisition:
            # with a check outside the lock, an add_request landing between
            # check and step would be missed by this drain pass
            with self._trace_lock:
                if not (self.has_work or self.busy):
                    break
                out.extend(self.loop.step(self._now_fn()))
        return out

    # -------------------------------------------------------- checkpointing
    def snapshot_state(self) -> dict:
        """Scheduler + KV state for engine checkpoint/restart (in-flight
        micro-batches are recovered by recompute: anything in the ring is
        folded back into the waiting queue)."""
        reqs = []
        seen = set()
        for group in (list(self.scheduler.waiting),
                      self.scheduler.running_prefill,
                      self.scheduler.running_decode):
            for r in group:
                if r.request_id in seen:
                    continue
                seen.add(r.request_id)
                reqs.append({
                    "request_id": r.request_id,
                    "prompt": list(r.prompt_token_ids),
                    "output": list(r.output_token_ids),
                    "max_new_tokens": r.sampling.max_new_tokens,
                    "arrival": r.metrics.arrival_time,
                })
        return {"requests": reqs, "ticks": self.stats.ticks}

    @staticmethod
    def restore_requests(engine: "PipelineEngine", snap: dict) -> None:
        for r in snap["requests"]:
            req = Request(r["request_id"], list(r["prompt"]),
                          SamplingParams(max_new_tokens=r["max_new_tokens"]))
            req.output_token_ids = list(r["output"])
            req.metrics.arrival_time = r["arrival"]
            # recompute semantics: prompt+outputs re-prefill from scratch
            engine.scheduler.add_request(req)
