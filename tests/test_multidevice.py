"""Multi-device pipeline tests (subprocess: they need
--xla_force_host_platform_device_count, which must NOT leak into the other
tests' single-device jax runtime).

These programs keep the `tensor` axis auto-sharded inside shard_map
(partial-auto lowering).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_pipelined_train_loss_decreases():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, make_reduced
        from repro.distributed.pipeline import build_train_step
        from repro.distributed.optimizer import adam_init
        from repro.models import transformer as tfm

        mesh = jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        cfg = make_reduced(get_config("qwen1.5-0.5b")).with_plan(pp=2, tp=2)
        cfg = dataclasses.replace(cfg, dtype="float32")
        with jax.set_mesh(mesh):
            step = jax.jit(build_train_step(cfg, mesh))
            params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
            pspecs = tfm.param_pspecs(cfg)
            params = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                params, pspecs, is_leaf=lambda x: isinstance(x, P))
            opt = adam_init(params)
            rng = np.random.default_rng(0)
            batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 2, 32)), jnp.int32),
                     "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 2, 32)), jnp.int32)}
            losses = []
            for _ in range(6):
                params, opt, m = step(params, opt, batch)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("LOSSES", losses[0], losses[-1])
    """)
    assert "LOSSES" in out


@pytest.mark.slow
def test_pipeline_loss_matches_dense_reference():
    """The pp=2/tp=2 train step's loss (pipeline + vocab-sharded xent) must
    equal the dense single-device cross-entropy on the same batch."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, make_reduced
        from repro.distributed.optimizer import AdamConfig, adam_init
        from repro.distributed.pipeline import build_train_step
        from repro.models import transformer as tfm
        from repro.models.reference import dense_forward

        mesh = jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        cfg = make_reduced(get_config("internlm2-1.8b")).with_plan(pp=2, tp=2)
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        M, mb, T = 4, 2, 16
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (M, mb, T)), jnp.int32)
        labs = jnp.asarray(rng.integers(0, cfg.vocab_size, (M, mb, T)), jnp.int32)
        with jax.set_mesh(mesh):
            # lr=0 so the returned loss is exactly f(params) on this batch
            step = jax.jit(build_train_step(cfg, mesh, adam=AdamConfig(lr=0.0),
                                            aux_coef=0.0))
            pd = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                              params, tfm.param_pspecs(cfg),
                              is_leaf=lambda x: isinstance(x, P))
            _, _, metrics = step(pd, adam_init(pd), {"tokens": toks, "labels": labs})
            got = float(metrics["loss"])

        logits = np.asarray(dense_forward(cfg, params, toks.reshape(M*mb, T)),
                            np.float32)
        flat_l = np.asarray(labs).reshape(M*mb, T)
        lse = jax.nn.logsumexp(jnp.asarray(logits), axis=-1)
        gold = np.take_along_axis(logits, flat_l[..., None], axis=-1)[..., 0]
        want = float(np.mean(np.asarray(lse) - gold))
        assert abs(got - want) < 2e-4, (got, want)
        print("PIPELINE_LOSS_MATCH", got, want)
    """)
    assert "PIPELINE_LOSS_MATCH" in out


@pytest.mark.slow
def test_serve_tick_multistage_engine_equivalence():
    """Engine on a pp=2 mesh produces the dense reference's greedy tokens."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, make_reduced
        from repro.core import SamplingParams, ThrottleConfig
        from repro.models import transformer as tfm
        from repro.models.reference import greedy_generate
        from repro.models.serve import ServeDims
        from repro.runtime.engine import PipelineEngine

        mesh = jax.make_mesh((1, 2, 2), ("data", "stage", "tensor"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        cfg = make_reduced(get_config("qwen1.5-0.5b")).with_plan(pp=2, tp=2)
        cfg = dataclasses.replace(cfg, dtype="float32")
        dims = ServeDims(Sp=1, C=16, Sd=8, pages=256, page=8, Bp=32, Bd=32, slots=16)
        with jax.set_mesh(mesh):
            params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
            pspecs = tfm.param_pspecs(cfg)
            params = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                                  params, pspecs, is_leaf=lambda x: isinstance(x, P))
            th = ThrottleConfig(pipeline_depth=2, max_prefill_tokens=16,
                                min_prefill_tokens=4, num_iters_T=2)
            eng = PipelineEngine(cfg, dims, params, mesh, th)
        rng = np.random.default_rng(5)
        prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (9, 21)]
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=5)) for p in prompts]
        eng.drain(max_ticks=400)
        for p, r in zip(prompts, reqs):
            want = greedy_generate(cfg, params, p, 5)
            assert r.output_token_ids == want, (r.output_token_ids, want)
        print("SERVE_MULTISTAGE_MATCH")
    """)
    assert "SERVE_MULTISTAGE_MATCH" in out


@pytest.mark.slow
def test_ep_moe_train_and_grad_compression():
    """Expert-parallel MoE over the data axis + int8/ring8 grad compression
    all lower, run, and keep the loss finite & decreasing."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, make_reduced
        from repro.distributed.pipeline import build_train_step
        from repro.distributed.optimizer import adam_init
        from repro.models import transformer as tfm

        mesh = jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        cfg = make_reduced(get_config("kimi-k2-1t-a32b")).with_plan(pp=2, tp=2)
        cfg = dataclasses.replace(cfg, dtype="float32")
        assert cfg.plan.ep_over_data
        for mode in (None, "int8", "ring8"):
            with jax.set_mesh(mesh):
                step = jax.jit(build_train_step(cfg, mesh, grad_compression=mode))
                params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
                pspecs = tfm.param_pspecs(cfg)
                params = jax.tree.map(
                    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                    params, pspecs, is_leaf=lambda x: isinstance(x, P))
                opt = adam_init(params)
                rng = np.random.default_rng(0)
                batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 2, 32)), jnp.int32),
                         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 2, 32)), jnp.int32)}
                losses = []
                for _ in range(4):
                    params, opt, m = step(params, opt, batch)
                    losses.append(float(m["loss"]))
            assert all(np.isfinite(losses)), (mode, losses)
            assert losses[-1] < losses[0], (mode, losses)
            print("MODE_OK", mode, round(losses[0], 3), round(losses[-1], 3))
    """, timeout=1200)
    assert out.count("MODE_OK") == 3
