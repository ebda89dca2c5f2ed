"""Engine perf trajectory harness: sync/async dispatch x fixed/bucketed
shapes on the exact reduced engine (DESIGN.md §12).

Runs the same mixed prefill/decode workload through all four dispatch/shape
variants of `PipelineEngine`, asserts their greedy outputs are bit-identical
(scheduling and padding must never change results — the Table-1 claim), and
writes ``BENCH_engine.json`` at the repo root:

    tokens_per_s        end-to-end decode throughput over the serve loop
    host_s_per_tick     host-side work per tick (prepare/meta/fresh/dispatch)
    readback_s_per_tick host time *blocked* on device token readback
                        (`tick.readback_wait`)
    host_wait_per_tick  the sum — everything the host cannot overlap
    padded_ratio        padded tokens / (scheduled + padded) per class
    scanned_pages       KV pages the attention scan walked (bucket width)
    live_pages          KV pages actually holding context
    attn_padded_ratio   1 - live/scanned — dead-page scan waste (schema /2)

The checked-in JSON is the perf trajectory record: regenerate with
``python benchmarks/bench_engine.py`` after engine changes and commit the
diff.  ``--smoke`` runs a seconds-scale version of the same loop (CI's
``make bench-smoke``) and validates the document schema without touching
the checked-in file; ``--validate PATH`` only re-validates an existing
document.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT / "src"))

import jax  # noqa: E402

BENCH_SCHEMA = "gllm-bench-engine/2"

VARIANTS = {
    "sync_fixed": dict(async_dispatch=False, bucketed=False),
    "sync_bucketed": dict(async_dispatch=False, bucketed=True),
    "async_fixed": dict(async_dispatch=True, bucketed=False),
    "async_bucketed": dict(async_dispatch=True, bucketed=True),
}
BASELINE = "sync_fixed"
CANDIDATE = "async_bucketed"


def build_engine(params_cache: dict, *, d_model: int, variant_kw: dict):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, make_reduced
    from repro.core import ThrottleConfig
    from repro.models import transformer as tfm
    from repro.models.serve import ServeDims
    from repro.runtime.engine import PipelineEngine

    cfg = make_reduced(get_config("qwen1.5-0.5b"), d_model=d_model).with_plan(
        pp=1, tp=1, ep_over_data=False)
    cfg = dataclasses.replace(cfg, dtype="float32",
                              moe_capacity_factor=float(
                                  max(cfg.num_experts, 1)))
    mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    dims = ServeDims(Sp=1, C=16, Sd=8, pages=256, page=8, Bp=32, Bd=32,
                     slots=16, Te=0)
    with jax.set_mesh(mesh):
        if "params" not in params_cache:
            params = tfm.init_params(cfg, jax.random.key(0),
                                     dtype=jnp.float32)
            params_cache["params"] = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                params, tfm.param_pspecs(cfg),
                is_leaf=lambda x: isinstance(x, P))
        th = ThrottleConfig(pipeline_depth=1, max_prefill_tokens=16,
                            min_prefill_tokens=4, num_iters_T=2)
        eng = PipelineEngine(cfg, dims, params_cache["params"], mesh, th,
                             **variant_kw)
    return cfg, eng


def workload(cfg, *, smoke: bool) -> List[dict]:
    """Deterministic mixed workload: three waves of requests with varied
    prompt lengths (single-chunk, multi-chunk) and decode lengths, so the
    ring sees bubbles, partial batches, and every bucket class."""
    import numpy as np
    rng = np.random.default_rng(2024)
    if smoke:
        lens = [(7, 3), (23, 3), (12, 2)]
        waves = [lens]
    else:
        waves = [
            [(7, 16), (23, 12), (12, 20), (40, 8)],
            [(5, 24), (33, 10), (18, 16), (9, 12)],
            [(27, 8), (14, 20), (6, 16), (21, 12)],
        ]
    out = []
    for wave in waves:
        out.append([
            dict(prompt=[int(t) for t in
                         rng.integers(0, cfg.vocab_size, int(plen))],
                 max_new=mnew)
            for plen, mnew in wave
        ])
    return out


def run_variant(name: str, params_cache: dict, waves, *,
                d_model: int) -> Dict[str, Any]:
    from repro.core import SamplingParams

    cfg, eng = build_engine(params_cache, d_model=d_model,
                            variant_kw=VARIANTS[name])
    # identical starting line for all four variants: ladder (or the single
    # full program) compiled before the clock starts
    if not eng.backend.bucketed:
        eng.backend.warm_start()
    compiles_warm = eng.backend.compile_count()

    reqs = []
    t0 = time.perf_counter()
    for wave in waves:
        for w in wave:
            reqs.append(eng.add_request(
                w["prompt"], SamplingParams(max_new_tokens=w["max_new"])))
        for _ in range(5):          # let the wave interleave with service
            eng.step()
    eng.drain(max_ticks=5000)
    wall = time.perf_counter() - t0

    assert all(r.is_finished for r in reqs), \
        f"{name}: unfinished requests {[r.state for r in reqs]}"
    st = eng.backend.stats
    compiles_final = eng.backend.compile_count()
    sched = st.scheduled_prefill + st.scheduled_decode
    padded = st.padded_prefill + st.padded_decode
    readback = st.phases.get("tick.readback_wait", 0.0)
    return {
        "outputs": [r.output_token_ids for r in reqs],
        "report": {
            "ticks": st.ticks,
            "tokens_out": st.tokens_out,
            "wall_s": round(wall, 4),
            "tokens_per_s": round(st.tokens_out / wall, 2) if wall else None,
            "host_s_per_tick": round(st.host_s / max(st.ticks, 1), 6),
            "readback_s_per_tick": round(readback / max(st.ticks, 1), 6),
            "host_wait_per_tick": round(
                (st.host_s + readback) / max(st.ticks, 1), 6),
            "padded_prefill": st.padded_prefill,
            "padded_decode": st.padded_decode,
            "scheduled_prefill": st.scheduled_prefill,
            "scheduled_decode": st.scheduled_decode,
            "padded_ratio": round(padded / max(sched + padded, 1), 4),
            "scanned_pages": st.scanned_pages,
            "live_pages": st.live_pages,
            "attn_padded_ratio": round(
                1.0 - st.live_pages / max(st.scanned_pages, 1), 4),
            "compiles_after_warm": compiles_warm,
            "recompiles_during_serve": compiles_final - compiles_warm,
        },
    }


def validate(doc: Dict[str, Any]) -> None:
    """Schema check for a bench document (no external deps): raises
    ValueError with the offending path on any violation."""
    def need(cond, path, msg):
        if not cond:
            raise ValueError(f"BENCH_engine.json invalid at {path}: {msg}")

    need(doc.get("schema") == BENCH_SCHEMA, "schema",
         f"expected {BENCH_SCHEMA!r}, got {doc.get('schema')!r}")
    need(isinstance(doc.get("config"), dict), "config", "missing dict")
    for k in ("arch", "d_model", "smoke"):
        need(k in doc["config"], f"config.{k}", "missing")
    need(isinstance(doc.get("variants"), dict), "variants", "missing dict")
    need(set(doc["variants"]) == set(VARIANTS), "variants",
         f"expected {sorted(VARIANTS)}, got {sorted(doc['variants'])}")
    numeric = ("ticks", "tokens_out", "wall_s", "tokens_per_s",
               "host_s_per_tick", "readback_s_per_tick",
               "host_wait_per_tick", "padded_prefill", "padded_decode",
               "scheduled_prefill", "scheduled_decode", "padded_ratio",
               "scanned_pages", "live_pages", "attn_padded_ratio",
               "compiles_after_warm", "recompiles_during_serve")
    for vn, rep in doc["variants"].items():
        for k in numeric:
            need(isinstance(rep.get(k), (int, float)),
                 f"variants.{vn}.{k}", f"missing or non-numeric: "
                 f"{rep.get(k)!r}")
        need(0.0 <= rep["padded_ratio"] <= 1.0,
             f"variants.{vn}.padded_ratio", "out of [0, 1]")
        need(0.0 <= rep["attn_padded_ratio"] <= 1.0,
             f"variants.{vn}.attn_padded_ratio", "out of [0, 1]")
        need(0 <= rep["live_pages"] <= rep["scanned_pages"],
             f"variants.{vn}.live_pages",
             "must satisfy 0 <= live_pages <= scanned_pages")
    cmp_ = doc.get("comparison")
    need(isinstance(cmp_, dict), "comparison", "missing dict")
    for k in ("baseline", "candidate", "padded_ratio_reduced",
              "attn_padded_ratio_reduced", "host_wait_reduced",
              "tick_counts_sane", "outputs_bit_identical"):
        need(k in cmp_, f"comparison.{k}", "missing")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run; writes to a temp file unless "
                         "--out is given")
    ap.add_argument("--out", type=Path, default=None,
                    help=f"output path (default: {REPO_ROOT}/"
                         "BENCH_engine.json, or a temp file with --smoke)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="reduced model width (default 64 smoke / 256 full)")
    ap.add_argument("--validate", type=Path, default=None, metavar="PATH",
                    help="only validate an existing bench document and exit")
    args = ap.parse_args(argv)

    if args.validate is not None:
        validate(json.loads(args.validate.read_text()))
        print(f"{args.validate}: valid {BENCH_SCHEMA}")
        return 0

    d_model = args.d_model or (64 if args.smoke else 256)
    params_cache: dict = {}
    from repro.configs import get_config, make_reduced
    cfg = make_reduced(get_config("qwen1.5-0.5b"), d_model=d_model)
    waves = workload(cfg, smoke=args.smoke)

    results = {}
    for name in VARIANTS:
        print(f"[bench_engine] running {name} ...", flush=True)
        results[name] = run_variant(name, params_cache, waves,
                                    d_model=d_model)

    identical = all(results[n]["outputs"] == results[BASELINE]["outputs"]
                    for n in VARIANTS)
    base = results[BASELINE]["report"]
    cand = results[CANDIDATE]["report"]
    # tick-count sanity (async inflation regression, DESIGN.md §12): deferred
    # retirement must not materially inflate device ticks vs the sync variant
    # on the same workload
    ticks_sane = all(
        results[f"async_{s}"]["report"]["ticks"]
        <= results[f"sync_{s}"]["report"]["ticks"] * 1.15 + 2
        for s in ("fixed", "bucketed"))
    doc = {
        "schema": BENCH_SCHEMA,
        "config": {
            "arch": "qwen1.5-0.5b (reduced)",
            "d_model": d_model,
            "smoke": args.smoke,
            "requests": sum(len(w) for w in waves),
            "platform": "cpu",
        },
        "variants": {n: results[n]["report"] for n in VARIANTS},
        "comparison": {
            "baseline": BASELINE,
            "candidate": CANDIDATE,
            "padded_ratio_reduced":
                cand["padded_ratio"] < base["padded_ratio"],
            "attn_padded_ratio_reduced":
                cand["attn_padded_ratio"] < base["attn_padded_ratio"],
            "host_wait_reduced":
                cand["host_wait_per_tick"] < base["host_wait_per_tick"],
            "tick_counts_sane": ticks_sane,
            "outputs_bit_identical": identical,
        },
    }
    validate(doc)

    if args.out is not None:
        out = args.out
    elif args.smoke:
        out = Path(tempfile.mkdtemp(prefix="bench_engine_")) \
            / "BENCH_engine.json"
    else:
        out = REPO_ROOT / "BENCH_engine.json"
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"[bench_engine] wrote {out}")
    for n, r in doc["variants"].items():
        print(f"  {n:15s} tok/s={r['tokens_per_s']:>8} "
              f"host_wait/tick={r['host_wait_per_tick']:.6f} "
              f"padded_ratio={r['padded_ratio']:.4f} "
              f"attn_padded_ratio={r['attn_padded_ratio']:.4f} "
              f"recompiles={r['recompiles_during_serve']}")
    print(f"  comparison: {doc['comparison']}")

    if not identical:
        print("[bench_engine] FAIL: variant outputs diverged", file=sys.stderr)
        return 1
    if not ticks_sane:
        print("[bench_engine] FAIL: async dispatch inflated tick counts "
              "vs sync", file=sys.stderr)
        return 1
    if not args.smoke and not (doc["comparison"]["padded_ratio_reduced"]
                               and doc["comparison"]["attn_padded_ratio_reduced"]
                               and doc["comparison"]["host_wait_reduced"]):
        print(f"[bench_engine] FAIL: {CANDIDATE} does not strictly improve "
              f"on {BASELINE}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
