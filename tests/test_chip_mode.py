"""The chip build mode (`EngineSpec(reduced=False)`), checked on the CPU
without allocating the model: published widths at full depth in bf16, one
pipeline stage per device, the pool sized from device memory, and where the
compile cache goes."""

import importlib
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import serve as serve_lib
from repro.models import transformer as tfm
from repro.serving import EngineSpec, ServeSpec

build_mod = importlib.import_module("repro.serving.build")

QWEN = "qwen1.5-0.5b"


@pytest.mark.parametrize("pp", [1, 4])
def test_published_depth_on_stages(pp):
    cfg = get_config(QWEN).on_stages(pp)
    assert cfg.num_layers == 24
    assert cfg.plan.pp == pp and cfg.plan.tp == 1
    assert cfg.layers_per_stage * pp == cfg.num_layers
    assert cfg.dtype == "bfloat16"
    assert (cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (1024, 16, 64, 2816, 151936)
    params = jax.eval_shape(
        lambda key: tfm.init_params(cfg, key, dtype=jnp.dtype(cfg.dtype)),
        jax.random.key(0))
    leaves = jax.tree.leaves(params)
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    wq = params["stages"]["b0_attn_mlp"]["wq"]
    assert wq.shape == (pp, 24 // pp, 1024, 1024)
    # untied embedding and head: 0.62 B parameters, whatever the split
    assert sum(a.size for a in leaves) == 619_570_176


@pytest.mark.parametrize("pp", [5, 7, 48])
def test_non_dividing_stages_raise(pp):
    with pytest.raises(ValueError, match="do not split"):
        get_config(QWEN).on_stages(pp)


def test_mixed_pattern_raises():
    with pytest.raises(ValueError, match="mixed block pattern"):
        get_config("jamba-1.5-large-398b").on_stages(1)


@pytest.mark.parametrize("pp", [1, 4])
def test_pool_fits_the_free_memory(pp):
    cfg = get_config(QWEN).on_stages(pp)
    free = 14 * 2**30
    dims = build_mod.chip_serve_dims(cfg, free)
    assert dims.slots == dims.pages >= max(dims.Bp, dims.Bd)
    assert dims.page * dims.Bp == 4096
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        serve_lib.abstract_caches(cfg, dims))) // pp
    with_copy = pool * (1 + build_mod._KV_COPY_FACTOR)
    assert with_copy <= free - build_mod._CHIP_RESERVE_BYTES
    # and one more page would not fit
    assert with_copy * (dims.pages + 1) / dims.pages > \
        free - build_mod._CHIP_RESERVE_BYTES
    with pytest.raises(ValueError, match="max-length sequence"):
        build_mod.chip_serve_dims(cfg, build_mod._CHIP_RESERVE_BYTES)


def test_stages_field_round_trips_and_validates():
    spec = ServeSpec(engine=EngineSpec(reduced=False, stages=4))
    assert ServeSpec.from_json(spec.to_json()) == spec
    assert ServeSpec.from_json(spec.to_json()).engine.stages == 4
    with pytest.raises(ValueError, match="chip mode"):
        EngineSpec(stages=2)                    # reduced mode has one stage
    with pytest.raises(ValueError, match=">= 1"):
        EngineSpec(reduced=False, stages=0)


def test_chip_mode_refuses_a_cpu():
    """The chip mode sizes its pool from device memory; a CPU reports none,
    so the build stops instead of falling back."""
    with pytest.raises(RuntimeError, match="needs an accelerator"):
        build_mod._free_bytes(build_mod.chip_mesh(1))


def test_chip_mesh_needs_enough_devices():
    with pytest.raises(ValueError, match="devices"):
        build_mod.chip_mesh(len(jax.devices()) + 1)


def test_compile_cache_placement(monkeypatch):
    """On an accelerator the cache goes to $JAX_COMPILATION_CACHE_DIR when
    set (JAX reads it; nothing else is set), else to the checkout's fixed,
    git-ignored `.jax_cache`."""
    repo = Path(__file__).resolve().parent.parent
    assert build_mod._COMPILE_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        build_mod._use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        build_mod._use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
