"""`build(spec) -> LLMServer`: materialize a `ServeSpec` (DESIGN.md §10).

The four shapes, one factory:

  * engine, 1 replica      -> `PipelineEngine` (exact jitted SPMD tick)
  * engine, N replicas     -> `ReplicaRouter` over N engines sharing one
                              read-only parameter tree
  * sim, 1 or N replicas   -> `PipelineSimulator` / `SimCluster` on the
                              calibrated roofline cost model
  * trace replay           -> the recorded stream (strict bit-identity via
                              `LLMServer.replay()`, or a timing-only engine
                              that serves new requests at recorded costs)

This module owns all construction; the spec layer stays pure data and the
launchers/benchmarks/examples stay thin flag->spec translations.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
from pathlib import Path
from typing import Any, List, Optional, Tuple

from repro.serving.server import LLMServer
from repro.serving.spec import ServeSpec, TraceSpec

# Compile-cache directory used when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed, git-ignored path inside the checkout.
_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# Reduced-mode defaults: small enough that the exact engine executes on a
# CPU container, throttle horizons scaled to the toy bucket (the same
# numbers every example and integration test has been using).
_REDUCED_THROTTLE = dict(num_iters_T=4, max_prefill_tokens=32,
                         min_prefill_tokens=4)
_REDUCED_DIMS = dict(Sp=1, C=32, Sd=8, pages=512, page=8, Bp=64, Bd=64,
                     slots=16)

# Chip-mode serve geometry, sized for one TPU v5e chip (16 GiB of HBM) per
# pipeline stage: a max model length of 4096 tokens (256 pages of 16),
# prefill chunks of C=512 tokens in Sp=2 rows, and Sd=64 decode rows.  The
# page pool is whatever the device's memory holds after the weights and the
# tick's temporaries (`chip_serve_dims`).
_CHIP_DIMS = dict(Sp=2, C=512, Sd=64, page=16, Bp=256, Bd=256)
# Device memory kept free for the tick program's temporaries (activations,
# [rows, vocab] logits, sampling) and for the runtime's own buffers: about
# 0.9e9 bytes for Qwen1.5-0.5B on a v5e.
_CHIP_RESERVE_BYTES = 3 << 29
# Headroom kept beside the KV pool, at this many times its bytes.  The tick
# copies none of the pool (it is stored in the kernel's own tiles, DESIGN.md
# §6), so the room is unused; it stays until a change meant to resize the
# pool, an input of Token Throttling, reclaims it.
_KV_COPY_FACTOR = 2


def build(spec: ServeSpec) -> LLMServer:
    """The one public entry point: every serving scenario starts here."""
    if spec.backend == "trace":
        return _build_trace_server(spec)
    if spec.backend == "sim":
        engine, cfg = _build_sim(spec)
    else:
        engine, cfg = _build_engine(spec)
    return LLMServer(engine, spec=spec, cfg=cfg)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def _throttle_config(spec: ServeSpec, pipeline_depth: int, *,
                     reduced: bool):
    from repro.core import PrefillPolicy, ThrottleConfig
    kw = dict(_REDUCED_THROTTLE) if reduced else {}
    kw.update(pipeline_depth=pipeline_depth,
              policy=PrefillPolicy(spec.engine.policy))
    kw.update(spec.engine.throttle or {})
    return ThrottleConfig(**kw)


def _build_engine(spec: ServeSpec) -> Tuple[Any, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, make_reduced
    from repro.models import transformer as tfm
    from repro.models.serve import ServeDims
    from repro.runtime.engine import PipelineEngine

    _use_compile_cache()
    es = spec.engine
    cfg = get_config(es.arch)
    if es.reduced:
        cfg = make_reduced(cfg, **(es.reduced_overrides or {})).with_plan(
            pp=1, tp=1, ep_over_data=False)
        cfg = dataclasses.replace(
            cfg, dtype="float32",
            moe_capacity_factor=float(max(cfg.num_experts, 1)))
        mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        dims_kw = dict(_REDUCED_DIMS,
                       Te=16 if cfg.is_encoder_decoder else 0)
        dims_kw.update(es.dims or {})
        dims = ServeDims(**dims_kw)
        th = _throttle_config(spec, 1, reduced=True)
    else:
        if es.reduced_overrides:
            raise ValueError(
                "EngineSpec.reduced_overrides only applies to reduced mode")
        cfg = cfg.on_stages(es.stages)       # published widths, bf16
        mesh = chip_mesh(es.stages)
        dims = None                 # sized below, once the weights are placed
        th = _throttle_config(spec, cfg.plan.pp, reduced=False)

    n = spec.num_replicas
    record = spec.trace.record if spec.trace is not None else None
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             tfm.param_pspecs(cfg),
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(
        lambda key: tfm.init_params(cfg, key, dtype=jnp.dtype(cfg.dtype)),
        out_shardings=shardings)(jax.random.key(es.seed))
    if dims is None:
        pages = (es.dims or {}).get("pages")
        if pages is None:
            dims = chip_serve_dims(cfg, _free_bytes(mesh) // n)
        else:
            dims = ServeDims(**_CHIP_DIMS, pages=pages, slots=pages)
        dims = dataclasses.replace(dims, **(es.dims or {}))
    # replicas share the (read-only) parameter tree; each owns its KV
    # pool, caches, scheduler, and TickLoop
    engines = [PipelineEngine(cfg, dims, params, mesh, th,
                              trace_path=_replica_trace(record, i, n),
                              async_dispatch=es.dispatch == "async",
                              bucketed=es.bucketed,
                              enable_prefix_caching=es.enable_prefix_caching)
               for i in range(n)]
    if spec.cluster is None and n == 1:
        return engines[0], cfg
    return _wrap_router(spec, engines, record), cfg


def _use_compile_cache() -> None:
    """Keep compiled programs across processes on an accelerator: in
    $JAX_COMPILATION_CACHE_DIR when it is set (JAX reads it itself, and no
    other directory is set here), else in `_COMPILE_CACHE_DIR`.  CPU runs,
    the test suite among them, cache nothing."""
    import jax
    if ("JAX_COMPILATION_CACHE_DIR" in os.environ
            or jax.default_backend() == "cpu"):
        return
    jax.config.update("jax_compilation_cache_dir", str(_COMPILE_CACHE_DIR))


def chip_mesh(stages: int):
    """Mesh (data=1, stage=`stages`, tensor=1) over the first `stages`
    devices JAX reports."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < stages:
        raise ValueError(f"{stages} pipeline stages need {stages} devices; "
                         f"JAX reports {len(devices)}")
    return Mesh(np.asarray(devices[:stages]).reshape(1, stages, 1),
                ("data", "stage", "tensor"),
                axis_types=(jax.sharding.AxisType.Auto,) * 3)


def _free_bytes(mesh) -> int:
    """Device memory not yet in use, on the fullest device of `mesh`."""
    free = []
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{d} reports no memory limit: the chip build mode sizes "
                "its KV pool from device memory and needs an accelerator "
                "(use EngineSpec(reduced=True) on a CPU)")
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return min(free)


def chip_serve_dims(cfg, free_bytes: int):
    """Chip-mode `ServeDims` for `cfg` with `free_bytes` of memory left on
    each device after the weights.

    The pool's unit is one KV page plus one state slot (``slots = pages``:
    every resident request holds at least one page, so slots never run out
    first).  A unit costs its cache bytes on one device, plus the headroom
    beside them (`_KV_COPY_FACTOR`), and `_CHIP_RESERVE_BYTES` stay free for
    the rest of the tick.
    """
    import jax

    from repro.models import serve as serve_lib
    from repro.models.serve import ServeDims

    one = ServeDims(**_CHIP_DIMS, pages=1, slots=1)
    unit = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        serve_lib.abstract_caches(cfg, one))) // cfg.plan.pp
    unit *= 1 + _KV_COPY_FACTOR
    pages = (free_bytes - _CHIP_RESERVE_BYTES) // unit
    need = max(_CHIP_DIMS["Bp"], _CHIP_DIMS["Bd"])
    if pages < need:
        raise ValueError(
            f"{cfg.name}: {free_bytes} free bytes hold {pages} KV pages, "
            f"fewer than one max-length sequence ({need})")
    return ServeDims(**_CHIP_DIMS, pages=int(pages), slots=int(pages))


def _replica_trace(record: Optional[str], i: int, n: int) -> Optional[str]:
    if record is None:
        return None
    return record if n == 1 else f"{record}.replica{i}"


def _wrap_router(spec: ServeSpec, replicas: List[Any],
                 record: Optional[str],
                 replica_factory: Optional[Any] = None):
    from repro.runtime.router import BalanceWeights, ReplicaRouter
    cl = spec.cluster
    weights = None
    if cl.cache_affinity is not None:
        weights = BalanceWeights(cache_affinity=cl.cache_affinity)
    return ReplicaRouter(
        replicas,
        policy=cl.route,
        weights=weights,
        rebalance=cl.rebalance,
        capacities=cl.capacities,
        roles=cl.roles,
        handoff=cl.handoff,
        autoscale=cl.autoscale,
        replica_factory=replica_factory,
        trace_path=None if record is None else f"{record}.router",
    )


# ---------------------------------------------------------------------------
# sim
# ---------------------------------------------------------------------------

def _build_sim(spec: ServeSpec) -> Tuple[Any, Any]:
    from repro.configs import get_config
    from repro.core import PagedKVManager, PipelineScheduler
    from repro.runtime.router import ReplicaRouter, SimCluster
    from repro.runtime.simulator import (PipelineSimulator, RuntimeModel,
                                         cost_model_for)

    cfg = get_config(spec.engine.arch)
    n = spec.num_replicas
    record = spec.trace.record if spec.trace is not None else None
    overrides = (spec.cluster.sim_overrides
                 if spec.cluster is not None else None)

    def replica_sim_spec(i: int):
        """The i-th replica's geometry: the base `SimSpec` with that
        replica's sparse overrides applied (spec-declared heterogeneity)."""
        ov = overrides[i] if overrides is not None else None
        return dataclasses.replace(spec.sim, **ov) if ov else spec.sim

    def one(i: int) -> PipelineSimulator:
        # ordinals >= the initial fleet size are autoscaler-added replicas:
        # they take the base geometry (sim_overrides shape the initial
        # fleet only — the elastic pool is homogeneous)
        ss = replica_sim_spec(i) if i < n else spec.sim
        th = _throttle_config(spec, ss.pp, reduced=False)
        runtime = (RuntimeModel.vllm_like() if ss.runtime == "vllm"
                   else RuntimeModel.gllm())
        kv = PagedKVManager(num_pages=ss.pages, page_size=ss.page_size,
                            enable_prefix_caching=ss.enable_prefix_caching)
        sched = PipelineScheduler(th, kv,
                                  max_model_len=ss.pages * ss.page_size)
        return PipelineSimulator(
            sched, ss.pp,
            cost_model_for(cfg, chips_per_stage=ss.chips_per_stage,
                           pp=ss.pp),
            runtime,
            straggler_stage=ss.straggler_stage,
            straggler_factor=ss.straggler_factor,
            # clusters record via SimCluster's trace_dir layout instead
            trace_path=record if spec.cluster is None else None)

    sims = [one(i) for i in range(n)]
    if spec.cluster is None and n == 1:
        return sims[0], cfg
    router = _wrap_router(spec, sims, None, replica_factory=one)
    # SimCluster owns cluster trace layout: one tick trace per replica plus
    # the router placement stream, under `record` as a directory
    return SimCluster(sims, router, trace_dir=record), cfg


# ---------------------------------------------------------------------------
# trace replay
# ---------------------------------------------------------------------------

class TraceReplayEngine:
    """Engine-surface adapter over a recorded trace in *timing-only* mode:
    new requests are welcome, the scheduler decides freely, and each tick
    costs what the recorded tick cost — the what-if serving substrate.
    Once the recording's ticks are exhausted, further ticks advance a
    fixed 1ms synthetic clock (matching `replay_trace`)."""

    def __init__(self, trace) -> None:
        from repro.runtime.core import TickLoop
        from repro.runtime.trace import TraceBackend, scheduler_from_header

        self.trace = trace
        self.scheduler = scheduler_from_header(trace.header)
        self.backend = TraceBackend(trace, TraceBackend.TIMING)
        self.loop = TickLoop(self.scheduler, self.backend)
        self._now = 0.0
        self._seq = itertools.count()
        self.recorder = None

    # ------------------------------------------------------- engine surface
    @property
    def finished(self):
        return self.loop.finished

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    @property
    def busy(self) -> bool:
        return self.loop.busy

    @property
    def on_token(self):
        return self.loop.on_token

    @on_token.setter
    def on_token(self, fn) -> None:
        self.loop.on_token = fn

    def add_request(self, prompt, sampling=None, request_id=None):
        from repro.core import Request, SamplingParams
        rid = request_id or f"replay-{next(self._seq)}"
        req = Request(rid, list(prompt), sampling or SamplingParams())
        req.metrics.arrival_time = self._clock()
        self.scheduler.add_request(req)
        return req

    def step(self):
        now = self._clock()
        if self.backend._k >= len(self.backend._ticks):
            now = self._now = self._now + 1e-3
        self._now = max(self._now, now)
        return self.loop.step(now)

    def abort_request(self, request_id: str) -> bool:
        req = self.scheduler.abort_request(request_id, self._clock())
        if req is None:
            return False
        if req.is_finished:
            self.loop.finished.append(req)
        return True

    def _clock(self) -> float:
        return max(self._now, self.backend.clock())


def _build_trace_server(spec: ServeSpec) -> LLMServer:
    from repro.runtime.trace import Trace, TraceBackend

    trace = Trace.load(spec.trace.replay)
    if spec.trace.timing_only:
        engine = TraceReplayEngine(trace)
        return LLMServer(engine, spec=spec, replay=trace,
                         replay_mode=TraceBackend.TIMING)
    # strict replay: the workload IS the recording; LLMServer.replay()
    # reproduces it bit-for-bit (no interactive substrate to submit into)
    return LLMServer(None, spec=spec, replay=trace,
                     replay_mode=TraceBackend.STRICT)
