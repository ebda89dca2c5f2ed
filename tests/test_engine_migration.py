"""Engine-level live migration (DESIGN.md §9): a request migrated between
two `JaxBackend` replicas mid-decode must produce *exactly* the greedy
tokens of a dense full-recompute reference — migration, like scheduling,
must never change outputs (the paper's Table 1 claim extended across the
replica boundary).

Also pins the device-side transfer itself: KV pages gathered at the source
slots are bit-identical to the destination cache contents at the re-mapped
slots after import.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, make_reduced
from repro.core import SamplingParams, ThrottleConfig
from repro.models import transformer as tfm
from repro.models.reference import greedy_generate
from repro.models.serve import ServeDims
from repro.runtime.engine import PipelineEngine
from repro.runtime.router import ReplicaRouter


def build_pair(arch="qwen1.5-0.5b", *, pages=256, page=8):
    """Two engine replicas sharing one read-only parameter tree (the
    launcher's --replicas topology), plus the config/params for the dense
    reference."""
    cfg = make_reduced(get_config(arch)).with_plan(pp=1, tp=1,
                                                   ep_over_data=False)
    cf = float(max(cfg.num_experts, 1))
    cfg = dataclasses.replace(cfg, dtype="float32", moe_capacity_factor=cf)
    mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    dims = ServeDims(Sp=1, C=16, Sd=8, pages=pages, page=page, Bp=32, Bd=32,
                     slots=16, Te=0)
    th = ThrottleConfig(pipeline_depth=1, max_prefill_tokens=16,
                        min_prefill_tokens=4, num_iters_T=2)
    with jax.set_mesh(mesh):
        params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, tfm.param_pspecs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        engines = [PipelineEngine(cfg, dims, params, mesh, th)
                   for _ in range(2)]
    return cfg, params, engines


@pytest.fixture(scope="module")
def pair():
    return build_pair()


def test_migrated_request_matches_dense_reference(pair):
    cfg, params, (eng_a, eng_b) = pair
    router = ReplicaRouter([eng_a, eng_b])
    rng = np.random.default_rng(3)
    prompt = list(rng.integers(0, cfg.vocab_size, 21))
    max_new = 8

    req = eng_a.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    # decode a few tokens on A, then live-migrate to B (pp=1: the ring
    # drains every tick, so the request is always drainable between steps)
    for _ in range(200):
        eng_a.step()
        if req.num_output_tokens >= 3:
            break
    assert 0 < req.num_output_tokens < max_new
    out_before = list(req.output_token_ids)

    assert router.migrate_request(req.request_id, 0, 1)
    assert not eng_a.scheduler.kv.has_request(req.request_id)
    assert eng_b.scheduler.kv.has_request(req.request_id)
    assert req.request_id not in eng_a.slots.owner

    eng_b.drain(max_ticks=300)
    assert req.is_finished
    assert req.output_token_ids[:len(out_before)] == out_before
    want = greedy_generate(cfg, params, prompt, max_new)
    assert req.output_token_ids == want, (req.output_token_ids, want)


def test_mid_prefill_handoff_matches_dense_reference(pair):
    """The §15 disagg enabler: a request moved *mid-prefill* (chunk cursor
    and prefilled KV in flight, no decode token yet) must still produce
    exactly the dense reference's greedy tokens after the destination
    finishes the remaining chunks and all of decode."""
    cfg, params, (eng_a, eng_b) = pair
    router = ReplicaRouter([eng_a, eng_b])
    rng = np.random.default_rng(7)
    # several 16-token chunks' worth of prompt (dims.C == 16)
    prompt = list(rng.integers(0, cfg.vocab_size, 45))
    max_new = 6

    req = eng_a.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    moved = False
    for _ in range(200):
        eng_a.step()
        if 0 < req.num_prefilled < req.num_effective_prompt_tokens \
                and req.num_output_tokens == 0:
            # same mechanism the first-decode handoff plane uses
            if router._move_request(req.request_id, 0, 1, kind="handoff"):
                moved = True
                break
    assert moved, "never caught the request between prefill chunks"
    assert router.disagg_stats.handoffs == 1
    assert not eng_a.scheduler.kv.has_request(req.request_id)
    assert eng_b.scheduler.kv.has_request(req.request_id)
    # exactly the prefilled prefix is resident at the destination
    assert eng_b.scheduler.kv.num_tokens(req.request_id) == req.num_prefilled

    eng_b.drain(max_ticks=300)
    assert req.is_finished
    want = greedy_generate(cfg, params, prompt, max_new)
    assert req.output_token_ids == want, (req.output_token_ids, want)


def test_unmigrated_and_migrated_runs_agree(pair):
    """Two identical prompts, one served in place on A, one migrated to B
    mid-decode: token streams must be identical."""
    cfg, params, (eng_a, eng_b) = pair
    router = ReplicaRouter([eng_a, eng_b])
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(0, cfg.vocab_size, 13))
    max_new = 6

    stay = eng_a.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    eng_a.drain(max_ticks=300)
    assert stay.is_finished

    move = eng_a.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    for _ in range(200):
        eng_a.step()
        if move.num_output_tokens >= 2:
            break
    assert router.migrate_request(move.request_id, 0, 1)
    eng_b.drain(max_ticks=300)
    assert move.is_finished
    assert move.output_token_ids == stay.output_token_ids


def test_kv_pages_bit_identical_across_transfer(pair):
    cfg, params, (eng_a, eng_b) = pair
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, cfg.vocab_size, 19))
    req = eng_a.add_request(prompt, SamplingParams(max_new_tokens=12))
    for _ in range(200):
        eng_a.step()
        if req.num_output_tokens >= 4:
            break
    rid = req.request_id
    export = eng_a.scheduler.kv.export_kv(rid)
    payload = eng_a.backend.export_kv_pages(rid, export.slots)
    assert payload, "transformer must have paged KV leaves"

    dst_slots = eng_b.scheduler.kv.import_kv(export)
    eng_b.backend.import_kv_pages(rid, payload, dst_slots)
    after = eng_b.backend.export_kv_pages(rid, dst_slots)
    assert set(payload) == set(after)
    for key in payload:
        np.testing.assert_array_equal(np.asarray(payload[key]),
                                      np.asarray(after[key]))
    # cleanup so the module-scoped pair stays reusable
    eng_b.scheduler.kv.free(rid)
    drained = eng_a.scheduler.drain_request(rid)
    assert drained is req
    eng_a.scheduler.kv.free(rid)
    eng_a.backend.finish_request(req)
