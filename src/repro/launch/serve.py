"""Serving launcher: a thin flag->`ServeSpec` translation over the public
serving API (`repro.serving`, DESIGN.md §10).

By default the engine runs the same-family reduced config, which executes
on a CPU; --full is the chip mode: the published config at full depth in
bf16 on one TPU chip, its KV pool sized from the chip's memory.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 12 --rate 4 [--policy gllm|sarathi|no_wt|no_ut] \
        [--replicas 2 --route balanced|rr] \
        [--rebalance-interval 0.25 [--migrate]] \
        [--http 8000]

Every flag combination is exactly one `ServeSpec`: --dump-spec prints that
spec as JSON and exits, --spec FILE serves from a previously dumped spec
(flags other than the workload ones are ignored).  With --replicas N, N
data-parallel engine replicas (sharing one read-only parameter tree) are
fronted by a `ReplicaRouter`; --rebalance-interval turns on the periodic
control plane and --migrate allows live KV migration (DESIGN.md §9).

With --http PORT the launcher becomes the real frontend process: instead of
running the synthetic workload it serves the spec over HTTP
(`repro.serving.http`, DESIGN.md §11) until interrupted — generate,
streaming SSE, abort, and stats; see docs/quickstart.md for the curl
vocabulary.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _spec(*, arch: str, reduced: bool, policy: str, seed: int, replicas: int,
          route: str, rebalance_interval: float, migrate: bool,
          trace_out: str):
    from repro.serving import (ClusterSpec, EngineSpec, RebalancePolicy,
                               ServeSpec, TraceSpec)
    cluster = None
    if replicas > 1 or rebalance_interval is not None:
        rebalance = None
        if rebalance_interval is not None:
            rebalance = RebalancePolicy(interval=rebalance_interval,
                                        migrate=migrate)
        cluster = ClusterSpec(replicas=max(replicas, 1), route=route,
                              rebalance=rebalance)
    return ServeSpec(
        backend="engine",
        engine=EngineSpec(arch=arch, reduced=reduced, policy=policy,
                          seed=seed),
        cluster=cluster,
        trace=TraceSpec(record=trace_out) if trace_out is not None else None,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--policy", default="gllm",
                    choices=["gllm", "sarathi", "no_wt", "no_ut"])
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the router")
    ap.add_argument("--route", default="balanced", choices=["balanced", "rr"],
                    help="request placement policy across replicas")
    ap.add_argument("--rebalance-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="run the periodic control plane: steal waiting "
                    "requests off saturated replicas every SECONDS")
    ap.add_argument("--migrate", action="store_true",
                    help="with --rebalance-interval: also live-migrate "
                    "running decode requests (KV moves, no recompute)")
    ap.add_argument("--full", action="store_true",
                    help="chip mode: the published config at full depth in "
                    "bf16 on one TPU chip")
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="serve from a ServeSpec JSON file instead of the "
                    "engine/cluster flags above")
    ap.add_argument("--dump-spec", action="store_true",
                    help="print the ServeSpec these flags translate to "
                    "(JSON) and exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a replayable tick trace of the run "
                    "(per-replica PATH.replicaN + PATH.router when N>1)")
    ap.add_argument("--trace-replay", default=None, metavar="PATH",
                    help="strict-replay a recorded trace through the "
                    "scheduler instead of serving (no accelerator needed)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the spec over HTTP on PORT (0 = ephemeral) "
                    "instead of running the synthetic workload")
    args = ap.parse_args()

    from repro.serving import SamplingParams, ServeSpec, TraceSpec, build

    if args.trace_replay is not None:
        # replay needs only the scheduler + the recorded events — it never
        # builds the model, so it runs on any box
        server = build(ServeSpec(backend="trace",
                                 trace=TraceSpec(replay=args.trace_replay)))
        server.replay()
        print(f"[replay {args.trace_replay}] {server.last_report.summary()} "
              f"— decisions match the recording")
        return

    if args.spec is not None:
        with open(args.spec) as fh:
            spec = ServeSpec.from_json(fh.read())
    else:
        spec = _spec(arch=args.arch, reduced=not args.full,
                     policy=args.policy, seed=0, replicas=args.replicas,
                     route=args.route,
                     rebalance_interval=args.rebalance_interval,
                     migrate=args.migrate, trace_out=args.trace_out)
    if args.dump_spec:
        print(spec.to_json(indent=2))
        return

    if args.http is not None:
        from repro.serving.http import HTTPFrontend
        frontend = HTTPFrontend(build(spec), port=args.http)
        print(f"[{spec.engine.arch} | {spec.backend}] serving on "
              f"{frontend.url} — POST /v1/generate[?stream=1], "
              f"DELETE /v1/requests/{{rid}}, GET /v1/stats  (Ctrl-C stops)")
        try:
            frontend.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            frontend.shutdown()
        return

    server = build(spec)
    cfg = server.cfg
    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = []
    for _ in range(args.requests):
        n = int(np.clip(rng.lognormal(3.0, 0.8), 4, 300))
        kw = {}
        if cfg.is_encoder_decoder:
            kw["enc_embeds"] = rng.normal(
                size=(server.replicas[0].dims.Te, cfg.d_model)
            ).astype(np.float32) * 0.05
        rids.append(server.submit(
            list(rng.integers(0, cfg.vocab_size, n)),
            SamplingParams(max_new_tokens=args.max_new), **kw))
    server.drain()
    wall = time.time() - t0
    outs = server.outputs(rids)
    stats = server.stats()
    toks = sum(len(o.token_ids) for o in outs)
    ttfts = [o.metrics.ttft() for o in outs if o.metrics.ttft() is not None]
    ticks = sum(r.ticks for r in stats.replicas)
    preempt = sum(r.preemptions for r in stats.replicas)
    pad = 0.0
    if spec.backend == "engine":    # bucket padding is an engine-only stat
        pad = sum(e.stats.padded_prefill for e in server.replicas) / max(
            1, sum(e.stats.ticks * max(e.dims.Sp, 1) * max(e.dims.C, 1)
                   for e in server.replicas))
    routed = ""
    if stats.routed_counts is not None:
        routed = (f" routed={'/'.join(map(str, stats.routed_counts))}"
                  f" ({server.router.policy.value})")
        if stats.rebalance is not None:
            routed += (f" rebalance[stolen={stats.rebalance.stolen} "
                       f"migrated={stats.rebalance.migrated}]")
    arch = spec.engine.arch
    print(f"[{arch} | {spec.engine.policy}] {len(outs)} requests, "
          f"{toks} tokens in {wall:.1f}s; ticks={ticks} "
          f"TTFT_mean={np.mean(ttfts)*1e3:.0f}ms "
          f"preemptions={preempt} "
          f"prefill-bucket padding={pad:.1%}{routed}")
    server.close()


if __name__ == "__main__":
    main()
