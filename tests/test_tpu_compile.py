"""Compiles for a described TPU v5e (no chip attached): the paged-attention
kernel at Qwen1.5-0.5B widths and the whole one-chip serve tick.  Nothing
runs; the TPU compiler refuses what the chip would refuse — misaligned
blocks, a kernel that cannot be partitioned, a program that does not fit.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and test workers import every
test file.
"""

import contextlib
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.pipeline import build_serve_tick
from repro.kernels.paged_attention import paged_flash_attention
from repro.launch.shapes import serve_input_specs
from repro.models import transformer as tfm

build_mod = importlib.import_module("repro.serving.build")

# memory_stats()["bytes_limit"] of one v5e chip (16 GiB of HBM)
BYTES_LIMIT = 16_909_336_064
H = KH = 16                             # Qwen1.5-0.5B attention widths
D = 64
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _persistent_cache_off():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


@pytest.fixture
def no_persistent_cache():
    with _persistent_cache_off():
        yield


def fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total <= BYTES_LIMIT, (total, ma)
    return total


@pytest.mark.parametrize("S,TQ,B,pages", [
    (64, 1, 256, 2048),      # decode: Sd=64 rows, 4096-token tables
    (2, 512, 256, 2048),     # prefill: Sp=2 chunks of C=512
])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          S, TQ, B, pages):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(paged_flash_attention).lower(
        sds((S, TQ, H, D), jnp.bfloat16),
        sds((pages, PAGE, KH * 2 * D), jnp.bfloat16),
        sds((S, B), jnp.int32), sds((S,), jnp.int32),
        sds((S, TQ), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    fits(compiled)


@pytest.fixture(scope="module")
def chip_tick(topo):
    """The chip mode's full-dims tick for Qwen1.5-0.5B (24 layers, pool
    sized for the chip's memory less the weights), compiled once with the
    TPU branch traced: (compiled, dims, params, pool leaf)."""
    from repro.kernels import ops
    from repro.models import serve as serve_lib
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1),
                ("data", "stage", "tensor"),
                axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = get_config("qwen1.5-0.5b").on_stages(1)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tfm.abstract_params(cfg), tfm.param_pspecs(cfg),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    dims = build_mod.chip_serve_dims(cfg, BYTES_LIMIT - weights)
    pool = serve_lib.abstract_caches(cfg, dims)["b0_attn_mlp"]["kv"]
    with pytest.MonkeyPatch.context() as mp, _persistent_cache_off():
        mp.setattr(ops, "on_tpu", lambda: True)   # trace the TPU branch
        tick, specs = build_serve_tick(cfg, mesh, dims)
        compiled = jax.jit(tick, donate_argnums=(1, 2)).lower(
            params, *serve_input_specs(cfg, dims, mesh, specs)).compile()
    return compiled, dims, params, pool


def test_one_chip_tick_compiles_with_kernel_and_fits(chip_tick):
    """The one-chip tick compiles with the kernel inside the fully manual
    shard_map and fits one chip."""
    compiled, _, params, _ = chip_tick
    # one prefill and one decode kernel call in the layer loop's body
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    # the embedding table is not a tick input, but stays on the chip
    embed = params["embed"]["tok"]
    assert fits(compiled) + embed.size * embed.dtype.itemsize <= BYTES_LIMIT


def _copied_sizes(hlo: str):
    """(name, elements) of every copy instruction in an HLO text."""
    out = []
    for m in re.finditer(r"%(\S+) = \(?\w+\[([\d,]*)\][^\n]*? "
                         r"copy(?:-start)?\(", hlo):
        out.append((m.group(1),
                    int(np.prod([int(x) for x in m.group(2).split(",") if x]))))
    return out


def test_one_chip_tick_leaves_the_pool_in_place(chip_tick):
    """The pool is stored lane-dense, [.., page, KH·2·hd], which is the
    kernel's own tiling: no op copies the pool or a layer's slice of it,
    the tick's scratch is about one layer's slice (a head_dim-64 pool stored
    [.., 2, KH, hd] was copied whole into a padded layout, 9.4 GB of
    scratch), the pool keeps the page count the chip mode sizes it to, and
    the tick still holds the two kernel calls."""
    compiled, dims, _, pool = chip_tick
    assert dims.pages == 2979
    assert pool.shape == (1, 24, dims.pages, PAGE, KH * 2 * D)
    layer = pool.size // pool.shape[1]
    hlo = compiled.as_text()
    assert _copied_sizes(hlo), "the copy pattern no longer matches the HLO"
    assert [c for c in _copied_sizes(hlo) if c[1] >= layer] == []
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_000_000_000
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
