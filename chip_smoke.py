"""Smoke run of the serving path on a TPU.

Serves Qwen1.5-0.5B at its published widths and full depth (24 layers,
d_model 1024, 16 heads of 64, d_ff 2816, vocab 151,936, bf16; random weights
from a seed) through the entry points a user calls:
``build(ServeSpec(engine=EngineSpec(reduced=False, ...)))`` -> `LLMServer`
-> `PipelineEngine` -> `TickLoop` with Token Throttling -> paged KV -> the
Pallas paged-attention kernel.

    python chip_smoke.py              # one chip: pp=1, bucketed, async
    python chip_smoke.py --chips 4    # pp=4 over four chips against pp=1

One chip: builds the server (its warm-up compiles the whole bucket ladder),
checks that the compiled tick holds the kernel, serves seeded requests whose
long prompts share ticks with decodes, checks that nothing compiled while
serving, and checks two short prompts against the dense reference
(`repro.models.reference`) on the same chip with the same bf16 weights.
Four chips: builds the same model at pp=4 (6 layers per stage, one stage
per chip) and the pp=1 engine on the first chip, serves the same prompts on
both, compares them by the same rule, and prints each chip's bytes in use.

The comparison rule, over the first `CHECKED_TOKENS` greedy steps: at each
step both paths have seen the same tokens, so their two largest log-probs
must agree within `TOL` (at the first step, that is the first-token
logits), and their tokens must agree wherever the reference's top-2 margin
exceeds `TOL`.  The weights are random, so near-ties are common: the first
tie the two paths break differently ends the comparison, since later steps
see different prefixes.

One process, no children.  Exits non-zero, printing no result, when JAX
finds no TPU.  Its last output line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "qwen1.5-0.5b"
SEED = 0
# long prompts span several C=512 chunks; short ones decode beside them
PROMPT_LENS = (1200, 700, 530, 24, 33, 96, 260, 48)
REFERENCE_PROMPTS = (3, 4)          # indices of the short prompts checked
CHECKED_TOKENS = 8
REFERENCE_WIDTH = 128               # padded width of the reference forward
# Log-prob tolerance between the serving path (paged KV, Pallas kernel,
# pipeline ticks) and the dense reference, both in bf16: 24 layers of bf16
# activations summed in different orders, and the reference rounds its
# attention probabilities to bf16 where the kernel keeps f32.  A v5e run
# measured first-token differences of 4.5e-5 and 6.6e-5.
TOL = 0.05


def log(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX reports platform "
                 f"{devices[0].platform!r}; this script runs only on a TPU")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips; JAX "
                 f"reports {len(devices)}")
    return devices


def prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    out = []
    for n in PROMPT_LENS:
        out.append([int(t) for t in rng.integers(0, vocab, n)])
    new = [int(k) for k in rng.integers(16, 33, len(PROMPT_LENS))]
    return out, new


def engine_spec(**kw):
    from repro.serving import EngineSpec, ServeSpec
    return ServeSpec(backend="engine",
                     engine=EngineSpec(arch=ARCH, reduced=False, seed=SEED,
                                       **kw))


def serve(server, prompt_list, new_tokens):
    """Submit every prompt, drain, and return their `RequestOutput`s."""
    from repro.serving import SamplingParams
    rids = [server.submit(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompt_list, new_tokens)]
    server.drain()
    outs = server.outputs(rids)
    for out, n in zip(outs, new_tokens):
        assert out.finish_reason == "length" and len(out.token_ids) == n, \
            (out.request_id, out.finish_reason, len(out.token_ids), n)
        assert len(out.top_logprobs) == n, out.request_id
    return outs


def compare(name, tokens, top2, ref_tokens, ref_top2):
    """Apply the comparison rule to one prompt; raise on a violation.
    `top2` / `ref_top2` are per-step (best, runner-up) log-probs.  Returns
    the largest log-prob difference seen."""
    diffs, tie = [], ""
    for k in range(CHECKED_TOKENS):
        diff = max(abs(a - b) for a, b in zip(top2[k], ref_top2[k]))
        assert diff <= TOL, \
            f"{name}: step {k} top-2 log-probs differ by {diff} > {TOL}"
        diffs.append(diff)
        if tokens[k] != ref_tokens[k]:
            margin = ref_top2[k][0] - ref_top2[k][1]
            assert margin <= TOL, (
                f"{name}: token {k} is {tokens[k]}, reference "
                f"{ref_tokens[k]} with margin {margin} > {TOL}")
            tie = f"; step {k} is a tie (margin {margin:.3g}) broken the " \
                  f"other way"
            break
    log(f"{name}: first-token log-prob diff {diffs[0]:.3g}, largest "
        f"{max(diffs):.3g} over {len(diffs)} steps with equal prefixes{tie}")
    return max(diffs)


def top2_of(logits):
    import numpy as np
    lp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
    part = np.sort(lp, axis=-1)[:, -2:]
    return [(float(b), float(a)) for a, b in part]


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def one_chip(devices) -> None:
    from repro.models.reference import greedy_generate_logits
    from repro.serving import build

    t0 = time.perf_counter()
    server = build(engine_spec(dispatch="async", bucketed=True))
    build_s = time.perf_counter() - t0
    cfg, engine = server.cfg, server.replicas[0]
    backend = engine.backend
    log(f"config {cfg.name}: {cfg.num_layers} layers "
        f"({cfg.layers_per_stage} per stage x pp={cfg.plan.pp}), d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads x {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, dtype {cfg.dtype}")
    log(f"serve dims {engine.dims}; ladder of {len(backend.ladder)} programs")
    log(f"build + warm_start (init, ladder compile) seconds {build_s:.3f}")
    warm = backend.compile_count()

    t0 = time.perf_counter()
    compiled = backend.lower_tick().compile()
    hlo = compiled.as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    ma = compiled.memory_analysis()
    log(f"full-dims tick recompiled for inspection in "
        f"{time.perf_counter() - t0:.3f}s: tpu_custom_call sites {kernels}; "
        f"temp bytes {ma.temp_size_in_bytes}, argument bytes "
        f"{ma.argument_size_in_bytes}")
    assert kernels > 0, "the compiled tick holds no Pallas kernel"

    prompt_list, new_tokens = prompts(cfg.vocab_size)
    t0 = time.perf_counter()
    reqs = serve(server, prompt_list, new_tokens)
    serve_s = time.perf_counter() - t0
    served = sum(len(r.token_ids) for r in reqs)
    log(f"served {len(reqs)} requests, {served} tokens, in "
        f"{backend.stats.ticks} ticks, {serve_s:.3f}s "
        f"(host clock, includes first-call overheads)")
    after = backend.compile_count()
    log(f"compile_count after warm_start {warm}, after serving {after}")
    assert after == warm, "the engine compiled while serving"

    for i in REFERENCE_PROMPTS:
        req = reqs[i]
        ref_tokens, ref_logits = greedy_generate_logits(
            cfg, engine.params, prompt_list[i], CHECKED_TOKENS,
            width=REFERENCE_WIDTH)
        compare(f"prompt {i} (len {len(prompt_list[i])})",
                req.token_ids, req.top_logprobs,
                ref_tokens, top2_of(ref_logits))
    log("reference check passed")
    log(f"device 0 memory {memory(devices[0])}")
    server.close()


def four_chips(devices, chips: int) -> None:
    from repro.serving import build

    # both engines share chip 0, so neither sizes its pool from free memory
    dims = {"pages": 1024}
    t0 = time.perf_counter()
    multi = build(engine_spec(stages=chips, dims=dims))
    log(f"pp={chips} engine built in {time.perf_counter() - t0:.3f}s: "
        f"{multi.cfg.layers_per_stage} layers per stage")
    for d in devices[:chips]:
        log(f"after pp={chips} build, device {d.id} memory {memory(d)}")
    t0 = time.perf_counter()
    single = build(engine_spec(stages=1, dims=dims))
    log(f"pp=1 engine built in {time.perf_counter() - t0:.3f}s")

    prompt_list, new_tokens = prompts(multi.cfg.vocab_size)
    got = serve(multi, prompt_list, new_tokens)
    want = serve(single, prompt_list, new_tokens)
    log(f"served {len(got)} requests at pp={chips} in "
        f"{multi.replicas[0].stats.ticks} ticks and at pp=1 in "
        f"{single.replicas[0].stats.ticks} ticks")
    worst = 0.0
    for i, (a, b) in enumerate(zip(got, want)):
        worst = max(worst, compare(
            f"prompt {i} (len {len(prompt_list[i])}) pp={chips} vs pp=1",
            a.token_ids, a.top_logprobs, b.token_ids, b.top_logprobs))
    log(f"pp={chips} agrees with pp=1 (largest log-prob diff {worst:.3g})")
    for d in devices[:chips]:
        log(f"end, device {d.id} memory {memory(d)}")
    multi.close()
    single.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the pp=4 against pp=1 phase")
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.chips == 1:
        one_chip(devices)
    else:
        four_chips(devices, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
