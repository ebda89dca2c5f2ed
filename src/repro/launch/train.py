"""Training launcher: pipelined train loop for any --arch with async
checkpointing and elastic restart.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 50 [--ckpt /tmp/ck --resume] [--grad-compression ring8]

Reduced configs, on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", default=None,
                    choices=[None, "int8", "ring8"])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, make_reduced
    from repro.data.tokens import batches
    from repro.distributed.optimizer import AdamConfig, adam_init
    from repro.distributed.pipeline import build_train_step
    from repro.models import transformer as tfm
    from repro.runtime.checkpoint import AsyncCheckpointer, restore_checkpoint

    cfg = make_reduced(get_config(args.arch)).with_plan(
        pp=1, tp=1, ep_over_data=False)
    cfg = dataclasses.replace(cfg, dtype="float32")
    mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    M, mbg, T = 2, mesh.shape["data"], args.seq
    ew = T // 2 if cfg.is_encoder_decoder else 0
    with jax.set_mesh(mesh):
        step = jax.jit(build_train_step(
            cfg, mesh, adam=AdamConfig(lr=args.lr), enc_width=ew,
            grad_compression=args.grad_compression))
        params = tfm.init_params(cfg, jax.random.key(0),
                                 dtype=jnp.dtype(cfg.dtype))
        if args.resume and args.ckpt and os.path.exists(
                os.path.join(args.ckpt, "manifest.json")):
            params = restore_checkpoint(args.ckpt, params)
            params = jax.tree.map(jnp.asarray, params)
            print(f"resumed from {args.ckpt}")
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, tfm.param_pspecs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        opt = adam_init(params)
        ck = AsyncCheckpointer() if args.ckpt else None
        data = batches(cfg.vocab_size, M, mbg, T, seed=0)
        t0 = time.time()
        for i in range(args.steps):
            b = {k: jnp.asarray(v) for k, v in next(data).items()}
            if cfg.family in ("vlm", "audio"):
                b["embeds"] = jnp.zeros((M, mbg, max(ew, 4), cfg.d_model),
                                        jnp.dtype(cfg.dtype))
            params, opt, m = step(params, opt, b)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(m['loss']):.4f} "
                      f"gnorm={float(m['gnorm']):.3f} "
                      f"({(i + 1) / (time.time() - t0):.2f} it/s)", flush=True)
            if ck and i % args.ckpt_every == args.ckpt_every - 1:
                ck.submit(args.ckpt, params, extra={"step": i})
        if ck:
            ck.wait()
            ck.close()
            print(f"checkpointed to {args.ckpt}")


if __name__ == "__main__":
    main()
