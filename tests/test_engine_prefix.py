"""Engine-level prefix caching (DESIGN.md §13): a request that adopts a
cached prefix — its first chunk resuming at `num_prefilled = cached`
over KV written by an *earlier* request — must produce exactly the greedy
tokens of the dense full-recompute reference.  Rotary positions make this
sharp: the adopted pages must hold the prefix at absolute positions
0..cached-1 or every downstream logit moves.

Also pins the serving-cost claim: adoption rides the existing chunked
prefill path, so the warm-started bucketed engine never recompiles for a
cache hit (`compile_count()` stays flat).
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


def build_engine(arch="qwen1.5-0.5b", *, pages=256, page=8):
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
        eng = PipelineEngine(cfg, dims, params, mesh, th,
                             enable_prefix_caching=True)
    return cfg, params, eng


@pytest.fixture(scope="module")
def setup():
    return build_engine()


def test_prefix_adopted_request_matches_dense_reference(setup):
    cfg, params, eng = setup
    rng = np.random.default_rng(7)
    shared = list(rng.integers(0, cfg.vocab_size, 24))    # 3 full pages
    tail_a = list(rng.integers(0, cfg.vocab_size, 9))
    tail_b = list(rng.integers(0, cfg.vocab_size, 5))
    max_new = 6

    r1 = eng.add_request(shared + tail_a, SamplingParams(max_new_tokens=max_new))
    eng.drain(max_ticks=500)
    assert r1.is_finished
    want1 = greedy_generate(cfg, params, shared + tail_a, max_new)
    assert r1.output_token_ids == want1, (r1.output_token_ids, want1)

    # r1's full prompt pages are now frozen in the prefix index; the
    # second request's head is served from them with zero recompute
    warm_compiles = eng.backend.compile_count()
    assert eng.scheduler.kv.peek_prefix((shared + tail_b)[:-1]) == 24
    hits_before = eng.scheduler.stats.prefix_hits

    r2 = eng.add_request(shared + tail_b, SamplingParams(max_new_tokens=max_new))
    eng.drain(max_ticks=500)
    assert r2.is_finished
    assert eng.scheduler.stats.prefix_hits == hits_before + 1
    assert eng.scheduler.stats.prefix_tokens_avoided >= 24
    want2 = greedy_generate(cfg, params, shared + tail_b, max_new)
    assert r2.output_token_ids == want2, (r2.output_token_ids, want2)
    # a cache hit is a data-path event, not a shape event: no recompiles
    assert eng.backend.compile_count() == warm_compiles
    eng.scheduler.check_invariants()


def test_identical_prompt_reuses_all_but_last_token(setup):
    cfg, params, eng = setup
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(0, cfg.vocab_size, 32))    # 4 full pages
    max_new = 5

    r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    eng.drain(max_ticks=500)
    avoided_before = eng.scheduler.stats.prefix_tokens_avoided

    # the probe drops the final prompt token (the first chunk must consume
    # it to sample from), so an identical re-ask reuses 3 of 4 pages
    r2 = eng.add_request(list(prompt), SamplingParams(max_new_tokens=max_new))
    eng.drain(max_ticks=500)
    assert r2.is_finished
    assert eng.scheduler.stats.prefix_tokens_avoided == avoided_before + 24
    assert r2.output_token_ids == r1.output_token_ids
    assert r1.output_token_ids == greedy_generate(cfg, params, prompt, max_new)
