"""Async double-buffered dispatch and bucketed serve shapes (DESIGN.md §12).

The four dispatch/shape variants of `PipelineEngine` — sync/async ×
fixed/bucketed — are pure execution strategies: they may change *when* a
tick's tokens are read back and *how much* padding a tick carries, never
the tokens themselves.  These tests pin that bit-identity, the
async+trace incompatibility, the zero-recompiles-in-steady-state contract
of the bucket ladder, and the drain/submit race on traced engines.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, make_reduced
from repro.core import SamplingParams, ThrottleConfig
from repro.models import transformer as tfm
from repro.models.serve import ServeDims
from repro.runtime.engine import PipelineEngine

VARIANTS = {
    "sync_fixed": dict(async_dispatch=False, bucketed=False),
    "sync_bucketed": dict(async_dispatch=False, bucketed=True),
    "async_fixed": dict(async_dispatch=True, bucketed=False),
    "async_bucketed": dict(async_dispatch=True, bucketed=True),
}


def build(arch="qwen1.5-0.5b", *, C=16, max_p=16, **engine_kw):
    cfg = make_reduced(get_config(arch)).with_plan(pp=1, tp=1,
                                                   ep_over_data=False)
    cf = float(max(cfg.num_experts, 1))   # dropless MoE: keep outputs exact
    cfg = dataclasses.replace(cfg, dtype="float32", moe_capacity_factor=cf)
    mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    dims = ServeDims(Sp=1, C=C, Sd=8, pages=256, page=8, Bp=32, Bd=32,
                     slots=16, Te=0)
    with jax.set_mesh(mesh):
        params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, tfm.param_pspecs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        th = ThrottleConfig(pipeline_depth=1, max_prefill_tokens=max_p,
                            min_prefill_tokens=4, num_iters_T=2)
        eng = PipelineEngine(cfg, dims, params, mesh, th, **engine_kw)
    return cfg, params, eng


def mixed_workload(cfg, eng):
    """Two waves with single-chunk, multi-chunk, and decode-heavy requests,
    interleaved with service so the ring sees bubbles and partial batches
    (every bucket class for the ladder, retirement lag for async)."""
    rng = np.random.default_rng(5)
    reqs = []
    for wave in ((7, 23, 37), (12, 5, 30)):
        for n in wave:
            reqs.append(eng.add_request(
                list(rng.integers(0, cfg.vocab_size, int(n))),
                SamplingParams(max_new_tokens=6)))
        for _ in range(4):
            eng.step()
    eng.drain(max_ticks=2000)
    assert all(r.is_finished for r in reqs), [r.state for r in reqs]
    return [r.output_token_ids for r in reqs]


def test_all_variants_bit_identical():
    """Padding shape and retirement timing must never change greedy tokens
    (the Table-1 claim extended to the dispatch layer)."""
    outs = {}
    for name, kw in VARIANTS.items():
        cfg, _, eng = build(**kw)
        outs[name] = mixed_workload(cfg, eng)
    for name in VARIANTS:
        assert outs[name] == outs["sync_fixed"], name


def test_async_dispatch_rejects_tracing():
    """Deferred retirement would interleave trace records out of order, so
    the ctor refuses the combination up front."""
    cfg = make_reduced(get_config("qwen1.5-0.5b")).with_plan(
        pp=1, tp=1, ep_over_data=False)
    dims = ServeDims(Sp=1, C=16, Sd=8, pages=256, page=8, Bp=32, Bd=32,
                     slots=16)
    th = ThrottleConfig(pipeline_depth=1, num_iters_T=2)
    with pytest.raises(ValueError, match="async_dispatch"):
        PipelineEngine(cfg, dims, None, None, th,
                       trace_path="unused.jsonl", async_dispatch=True)


def test_bucketed_zero_recompiles_after_warm():
    """`warm_start` (run by the ctor for bucketed engines) compiles the
    whole ladder; serving any mixed workload afterwards must not add a
    single compilation — the static-shape contract that keeps tick latency
    flat in steady state."""
    cfg, _, eng = build(bucketed=True)
    warm = eng.backend.compile_count()
    assert warm > 0
    mixed_workload(cfg, eng)
    assert eng.backend.stats.ticks > 0
    assert eng.backend.compile_count() == warm, \
        "bucketed serving recompiled after warm_start"


def test_bucketed_reduces_padded_tokens():
    """The point of the ladder: strictly fewer padded tokens than the
    fixed full-cell shape on the same workload."""
    padded = {}
    for name in ("sync_fixed", "sync_bucketed"):
        cfg, _, eng = build(**VARIANTS[name])
        mixed_workload(cfg, eng)
        st = eng.backend.stats
        padded[name] = st.padded_prefill + st.padded_decode
    assert padded["sync_bucketed"] < padded["sync_fixed"]


def test_depth_buckets_engage_and_never_recompile():
    """Deep-context requests walk the ladder's depth dimension (Bp/Bd) up
    from the shallow steps without a single post-warm compile, and shallow
    ticks actually select sub-full tables (scanned < full-width scan)."""
    cfg, _, eng = build(bucketed=True, C=16, max_p=16)
    warm = eng.backend.compile_count()
    rng = np.random.default_rng(3)
    # grows past page*Bd/4 = 64 tokens of context → crosses depth steps
    long = eng.add_request(list(rng.integers(0, cfg.vocab_size, 80)),
                           SamplingParams(max_new_tokens=40))
    short = eng.add_request(list(rng.integers(0, cfg.vocab_size, 5)),
                            SamplingParams(max_new_tokens=4))
    seen_bd = set()
    for _ in range(2000):
        if not (eng.has_work or eng.busy):
            break
        eng.step()
        if eng.stats.last_bucket is not None:
            seen_bd.add(eng.stats.last_bucket["Bd"])
    assert long.is_finished and short.is_finished
    assert len(seen_bd) > 1, f"depth never stepped: {seen_bd}"
    st = eng.backend.stats
    full_scan = st.ticks * (eng.dims.Sp * eng.dims.Bp
                            + eng.dims.Sd * eng.dims.Bd)
    assert st.scanned_pages < full_scan
    assert 0 < st.live_pages <= st.scanned_pages
    assert eng.backend.compile_count() == warm, \
        "depth bucketing recompiled after warm_start"


def test_async_tick_count_matches_sync():
    """Regression for the async tick inflation (51 vs 36 device ticks on the
    bench workload): with the readiness probe retiring finished batches
    before scheduling, async dispatch must not pay materially more device
    ticks than sync on the same workload."""
    ticks = {}
    for name in ("sync_bucketed", "async_bucketed"):
        cfg, _, eng = build(**VARIANTS[name])
        settle_each_tick(eng)
        mixed_workload(cfg, eng)
        ticks[name] = eng.backend.stats.ticks
    # identical once each readback is ready by the next step; the small
    # slack absorbs a genuinely in-flight device tick
    assert ticks["async_bucketed"] <= ticks["sync_bucketed"] * 1.15 + 2, ticks


def settle_each_tick(eng):
    """Let each dispatched tick finish on the device before the loop moves
    on.  The CPU backend dispatches asynchronously too, so without this the
    probe would see a tick still running whenever the host outpaces it —
    the overlap case, where deferring the retire is the intended behaviour
    and says nothing about the probe."""
    execute = eng.backend.execute

    def settled(*args):
        result = execute(*args)
        jax.block_until_ready(eng.backend.caches)
        return result

    eng.backend.execute = settled


def test_traced_drain_races_submissions(tmp_path):
    """Regression for the drain/submit race: `drain` checks has-work and
    ticks under ONE trace-lock acquisition, so a request submitted from
    another thread mid-drain is either served by this drain pass or left
    cleanly queued — and the recorded trace stays strictly replayable."""
    from repro.runtime.trace import Trace, replay_trace

    path = str(tmp_path / "race.trace.jsonl")
    cfg, _, eng = build(trace_path=path)
    rng = np.random.default_rng(9)
    prompts = [list(rng.integers(0, cfg.vocab_size, int(n)))
               for n in (6, 14, 9, 21, 11)]
    reqs = [eng.add_request(prompts[0], SamplingParams(max_new_tokens=4))]
    done = threading.Event()

    def submit():
        for p in prompts[1:]:
            time.sleep(0.002)
            reqs.append(eng.add_request(p, SamplingParams(max_new_tokens=4)))
        done.set()

    t = threading.Thread(target=submit)
    t.start()
    while not done.is_set() or eng.has_work or eng.busy:
        eng.drain(max_ticks=50)
    t.join()
    assert all(r.is_finished for r in reqs)
    eng.recorder.close()

    report = replay_trace(Trace.load(path))     # strict: decisions must match
    assert report.outputs() == {r.request_id: list(r.output_token_ids)
                                for r in reqs}
