"""Benchmark of the serving path on the chip: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A run builds the server of the cell's configuration through
`build(ServeSpec(...))`, puts the seed's weights (made here) into it, serves
one warm-up request, and counts that as set-up.  It then drives the cell's
traffic in an open loop: a lead-in the traffic file sets, not counted, and a
window of `--seconds`, in which every request is timed from when it was due.
With `--trace 1` a sub-window in the middle of the window is traced with
the profiler and the per-layer metrics are read over it; otherwise the
end-to-end metrics are read over the window.

Once the window has closed, the device's peak memory is read, the server is
freed, and a sample of the requests the window finished is compared with
the plain reference (`correct.py`).  Every number compared is printed beside
its limit, as the last lines on standard error and under "checks" in the
result, the last line on standard output.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, and when the program is not in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import manifest  # noqa: E402

# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = HERE / ".jax_cache"
OUT_DIR = HERE / ".out"
SUBWINDOW_S = 5.0           # traced part of the window (--trace 1)
WARMUP_PROMPT, WARMUP_TOKENS = 16, 4
MIN_COMPARED_TOKENS = 64
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def configure_jax():
    """Point JAX's compilation cache at `CACHE_DIR` (every program written,
    however fast it compiled) and keep libtpu's logs out of /tmp."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chips(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU found: JAX reports platform "
                 f"{devices[0].platform!r}; this benchmark runs only on a TPU")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips; JAX reports "
                 f"{len(devices)}")
    return devices


class CompileWatch:
    """Seconds and counts of JAX's compile-path events (tracing, lowering,
    compiling or loading from the cache), from `jax.monitoring`."""

    def __init__(self, jax) -> None:
        self.seconds = {e: 0.0 for e in COMPILE_EVENTS}
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        self.cache = {"hits": 0, "misses": 0}

        def on_duration(event, duration, **_):
            if event in self.seconds:
                self.seconds[event] += duration
                self.counts[event] += 1

        def on_event(event, **_):
            for k in self.cache:
                if event == f"/jax/compilation_cache/cache_{k}":
                    self.cache[k] += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def compiles(self) -> int:
        return self.counts["/jax/core/compile/backend_compile_duration"]

    def summary(self) -> str:
        parts = [f"{e.rsplit('/', 1)[1]} {self.counts[e]} in "
                 f"{self.seconds[e]:.3f}s" for e in COMPILE_EVENTS]
        return "; ".join(parts) + (f"; persistent cache hits "
                                   f"{self.cache['hits']}, misses "
                                   f"{self.cache['misses']}")


class GcWatch:
    """Pauses of Python's garbage collector: (start, seconds, generation),
    on the run's clock."""

    def __init__(self) -> None:
        self.pauses = []
        self._began = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.pauses.append((self._began, time.perf_counter()
                                - self._began, info["generation"]))
            self._began = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


def log_window(loop, gcw, w0: float, w1: float) -> None:
    """Log what the window held: its work, the in-system count at its start,
    the host's steps and the collector's pauses inside it."""
    import statistics

    due = [r for r in loop.records if w0 <= r.due_s < w1]
    open_at_w0 = sum(1 for r in loop.records if r.submit_s is not None
                     and r.submit_s < w0
                     and (r.finish_s is None or r.finish_s >= w0))
    log(f"window work: {len(due)} requests due, "
        f"{sum(len(r.planned.prompt) for r in due)} prompt and "
        f"{sum(r.planned.max_new_tokens for r in due)} output tokens; "
        f"{open_at_w0} requests open at its start")
    steps = sorted((d, t) for t, d in loop.steps if w0 <= t < w1)
    if steps:
        ds = [d for d, _ in steps]
        log(f"window steps: {len(ds)}, median "
            f"{statistics.median(ds) * 1e3:.1f} ms, over 0.5 s "
            f"{sum(d > 0.5 for d in ds)}, longest "
            + ", ".join(f"{d * 1e3:.0f} ms at {t:.1f}s"
                        for d, t in steps[-5:][::-1]))
    pauses = [(d, g) for t, d, g in gcw.pauses
              if w0 <= t - loop.t0 < w1]
    by_gen = {g: [d for d, gg in pauses if gg == g] for g in (0, 1, 2)}
    log("window gc: " + "; ".join(
        f"gen{g} {len(v)} in {sum(v) * 1e3:.1f} ms (longest "
        f"{max(v, default=0) * 1e3:.1f})" for g, v in by_gen.items()))


def check_program_config(cfg, server) -> None:
    """Raise unless the program serves the model the configuration file
    states."""
    pc, engine = server.cfg, server.replicas[0]
    stacked = pc.layers_per_stage * pc.plan.pp
    want = {"hidden_size": pc.d_model, "intermediate_size": pc.d_ff,
            "num_hidden_layers": stacked if pc.num_layers == stacked
            else f"{pc.num_layers} ({stacked} stacked)",
            "num_attention_heads": pc.num_heads,
            "num_key_value_heads": pc.num_kv_heads, "head_dim": pc.head_dim,
            "vocab_size": pc.vocab_size, "rope_theta": pc.rope_theta,
            "rms_norm_eps": pc.norm_eps, "qkv_bias": pc.qkv_bias,
            "torch_dtype": pc.dtype, "hidden_act": pc.act,
            "max_position_embeddings": engine.scheduler.max_model_len}
    bad = {k: (cfg[k], v) for k, v in want.items() if cfg[k] != v}
    if pc.norm != "rmsnorm" or pc.padded_vocab != pc.vocab_size:
        bad["norm / padded vocab"] = ("rmsnorm / vocab",
                                      f"{pc.norm} / {pc.padded_vocab}")
    if bad:
        raise ValueError(f"the program's {pc.name} differs from "
                         f"{cfg['name']}'s file (file, program): {bad}")


def build_server(cfg, seed: int):
    """The cell's server, holding the seed's weights; returns it and the
    weights (the benchmark's own arrays, kept for the reference)."""
    import jax
    from repro.serving import EngineSpec, ServeSpec, build

    import weights

    server = build(ServeSpec(backend="engine",
                             engine=EngineSpec(**cfg["engine"])))
    check_program_config(cfg, server)
    engine = server.replicas[0]
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), engine.params)
    # free the program's own random weights before making the seed's
    engine.params = engine.backend.params = None
    params = weights.make_program_params(cfg, seed, like)
    engine.params = engine.backend.params = params
    return server, params


def warm_up(server, vocab: int) -> None:
    """Serve one short request end to end."""
    import numpy as np
    from repro.serving import SamplingParams
    rid = server.submit(np.arange(WARMUP_PROMPT) % vocab,
                        SamplingParams(max_new_tokens=WARMUP_TOKENS))
    server.drain()
    out = server.get(rid)
    if len(out.token_ids) != WARMUP_TOKENS:
        raise RuntimeError(f"warm-up request served {len(out.token_ids)} "
                           f"tokens, not {WARMUP_TOKENS}")


def free_server(server) -> None:
    """Drop every device array the program holds."""
    engine = server.replicas[0]
    backend = engine.backend
    engine.params = backend.params = None
    backend.caches = backend.carry = None
    backend._ticks.clear()
    server.close()


class SubWindow:
    """The traced part of a `--trace 1` run: `SUBWINDOW_S` seconds in the
    middle of the window, under the profiler and the `bench.window` span,
    with the engine's and scheduler's counters read at its edges."""

    def __init__(self, jax, engine, loop, w0: float, seconds: float) -> None:
        self.jax, self.engine, self.loop = jax, engine, loop
        span = min(SUBWINDOW_S, seconds / 2)
        s0 = w0 + (seconds - span) / 2
        self.events = [(s0, self.start), (s0 + span, self.stop)]
        self.dir = OUT_DIR / "trace"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0    # the harness's spans suffice

    def _edge(self) -> dict:
        st = self.engine.backend.stats
        keys = ("ticks", "host_s", "padded_prefill", "padded_decode",
                "scheduled_prefill", "scheduled_decode")
        return {"t": time.perf_counter() - self.loop.t0,
                "engine": {k: getattr(st, k) for k in keys},
                "sched": len(self.engine.scheduler.stats
                             .scheduled_prefill_tokens)}

    def start(self) -> None:
        self.jax.profiler.start_trace(str(self.dir),
                                      profiler_options=self.options)
        self.span = self.jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.a = self._edge()

    def stop(self) -> None:
        self.b = self._edge()
        self.span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def read(self):
        """(counts of the sub-window, `trace_reduce.reduce` of its trace)."""
        from trace_reduce import read_xplane, reduce

        a, b = self.a, self.b
        ss = self.engine.scheduler.stats
        pairs = zip(ss.scheduled_prefill_tokens[a["sched"]:b["sched"]],
                    ss.scheduled_decode_tokens[a["sched"]:b["sched"]])
        counts = {"engine": {k: b["engine"][k] - a["engine"][k]
                             for k in a["engine"]},
                  "tick_tokens": [p + d for p, d in pairs if p + d],
                  "batches": [c for t, c in self.loop.batches
                              if a["t"] <= t <= b["t"]],
                  "seconds": b["t"] - a["t"]}
        xplanes = sorted(self.dir.rglob("*.xplane.pb"))
        reduced = reduce(read_xplane(str(xplanes[-1]))) if xplanes else None
        shutil.rmtree(self.dir, ignore_errors=True)
        return counts, reduced


def peaks_for(kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


def compared(cfg, found: dict, mismatches: int, compiles: int) -> dict:
    """Each number compared, its limit, and whether it keeps to it."""
    out = {k: {"value": found[k], "limit": cfg["check"][f"{k}_limit"],
               "bound": "at most"} for k in ("logit_gap", "logprob_error")}
    out["compared_tokens"] = {"value": found["tokens"],
                              "limit": MIN_COMPARED_TOKENS,
                              "bound": "at least"}
    out["length_mismatches"] = {"value": mismatches, "limit": 0,
                                "bound": "at most"}
    out["compiles_in_window"] = {"value": compiles, "limit": 0,
                                 "bound": "at most"}
    for c in out.values():
        c["ok"] = (c["value"] >= c["limit"] if c["bound"] == "at least"
                   else c["value"] <= c["limit"])
    return out


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             jax, watch=None, t_start: float = T_START) -> dict:
    """One run of `cell`; returns the result line's object."""
    from repro.serving import SamplingParams

    import correct
    from serve_loop import OpenLoop, attach_logprobs
    from traffic.generate import make_requests

    cfg, traffic = cell.config, cell.traffic
    server, params = build_server(cfg, seed)
    engine = server.replicas[0]
    warm_up(server, cfg["vocab_size"])
    warm_compiles = engine.backend.compile_count()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s; ladder of {len(engine.backend.ladder)} "
        f"programs; pool {engine.dims.pages} pages")
    if watch is not None:
        log(f"set-up compile path: {watch.summary()}")

    lead_in = float(traffic["lead_in_s"])
    w0, w1 = lead_in, lead_in + seconds
    plan = make_requests(traffic, seed=seed, duration_s=w1,
                         vocab=cfg["vocab_size"])
    loop = OpenLoop(server, plan, SamplingParams, count_batches=trace)
    sub = SubWindow(jax, engine, loop, w0, seconds) if trace else None
    compiles_before = watch.compiles if watch is not None else 0
    gcw = GcWatch()
    loop.run(time.perf_counter(), w1, sub.events if sub else ())
    gcw.close()
    log_window(loop, gcw, w0, w1)
    compiles = (engine.backend.compile_count() - warm_compiles
                + (watch.compiles - compiles_before if watch else 0))
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    log(f"device 0 memory: {stats}")

    records = loop.records
    in_window = [r for r in records if w0 <= r.due_s < w1]
    ctx = {"cfg": cfg, "records": records, "window_records": in_window,
           "window": (w0, w1), "setup_s": setup_s, "notes": {},
           "trace": None, "sub": None, "peaks": None}
    breakdown = None
    if sub is not None:
        ctx["sub"], ctx["trace"] = sub.read()
        ctx["peaks"] = peaks_for(device["kind"])
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = manifest.reader(m.name)(ctx)
        if value is None:
            log(f"metric {m.name}: nothing to read, left out")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}
    for k, v in ctx["notes"].items():
        log(f"note {k}: {v}")

    failed = sum(1 for r in in_window
                 if r.finish_reason not in (None, "length", "stop"))
    picked = correct.sample([r for r in records
                             if r.finish_s is not None and r.finish_s <= w1],
                            seed)
    attach_logprobs(server, picked)
    loop.server = None
    free_server(server)
    del server, engine
    gc.collect()
    t_ref = time.perf_counter()
    found = correct.compare(cfg, params, picked)
    log(f"reference over {found['requests']} requests, {found['tokens']} "
        f"served tokens, in {time.perf_counter() - t_ref:.3f}s")
    checks = compared(cfg, found, correct.length_mismatches(picked), compiles)
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": len(in_window), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']} ({c['bound']} {c['limit']})")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    cell = manifest.resolve(manifest.load(), args.workload)
    jax = configure_jax()
    devices = require_chips(jax, cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (fails here, before any result, without it)
    watch = CompileWatch(jax)
    log(f"{args.workload}: seed {args.seed}, {args.seconds}s, trace "
        f"{args.trace}, {len(devices)} x {devices[0].device_kind}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, jax=jax,
                      watch=watch)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
