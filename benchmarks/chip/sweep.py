"""Rate sweep of a cell's traffic on the chip, in one process, to find the
knee: the highest offered rate the server sustains without a growing queue.

    python3 benchmarks/chip/sweep.py --workload <cell> \
        --rates 1,1.5,2 --seconds 40 --seed 7

For each rate, in the order given, the cell's traffic at that rate serves a
lead-in (`--lead-in`, default the traffic file's) and a window of
`--seconds`; every second from the start the requests waiting for admission (the
scheduler's queue) and those submitted and not yet finished are sampled.  A
rate is sustained when the waiting queue grows over the window by less than
`GROWTH_LIMIT` of the requests offered (least-squares slope over the offered
rate).  A chat request lives for minutes on a slow server, so the number in
the system grows for that long even below the knee; the waiting queue grows
only once the server cannot admit what arrives.  Requests still open at the
window's end are aborted before the next rate.  The sweep stops once
`STOP_AFTER` rates in a row are not sustained.  Prints one JSON line per
rate.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import run  # noqa: E402  (puts this directory on the import path)

GROWTH_LIMIT = 0.05
STOP_AFTER = 2
SERIES_S = 10


def backlog_growth(samples) -> float:
    """Least-squares slope (requests per second) of (t, backlog) samples."""
    t, n = np.asarray(samples, float).T
    return float(np.polyfit(t, n, 1)[0])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lead-in", type=float, default=None)
    args = ap.parse_args(argv)
    cell = run.manifest.resolve(run.manifest.load(), args.workload)
    jax = run.configure_jax()
    run.require_chips(jax, cell.chips)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.serving import SamplingParams

    from serve_loop import OpenLoop, itls_s, tokens_in, ttfts_s
    from stats import percentile
    from traffic.generate import make_requests

    cfg = cell.config
    server, _ = run.build_server(cell, args.seed)
    run.warm_up(server, cfg["vocab_size"])
    run.log(f"set-up {time.perf_counter() - T_START:.3f}s")
    lead_in = (float(cell.traffic["lead_in_s"]) if args.lead_in is None
               else args.lead_in)
    sched = server.replicas[0].scheduler
    w0, w1 = lead_in, lead_in + args.seconds
    unsustained = 0
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate, lead_in_s=lead_in)
        plan = make_requests(traffic, seed=args.seed, duration_s=w1,
                             vocab=cfg["vocab_size"])
        loop = OpenLoop(server, plan, SamplingParams, tag=f"r{k}")
        samples = []

        def sample(t):
            open_ = sum(1 for r in loop.records if r.submit_s is not None
                        and r.finish_reason is None)
            samples.append((t, len(sched.waiting), open_))
        events = [(float(t), lambda t=t: sample(t))
                  for t in range(0, int(w1))]
        loop.run(time.perf_counter(), w1, events)
        win = [r for r in loop.records if w0 <= r.due_s < w1]
        ttft = ttfts_s(win, w1)
        gaps = itls_s(loop.records, w0, w1)
        samples_all, samples = samples, [x for x in samples if x[0] >= w0]
        growth = backlog_growth([(t, w) for t, w, _ in samples])
        sustained = growth < GROWTH_LIMIT * rate
        unsustained = 0 if sustained else unsustained + 1
        print(json.dumps({
            "rate_per_s": rate, "requests": len(win),
            "waiting_start": samples[0][1], "waiting_end": samples[-1][1],
            "waiting_growth_per_s": growth,
            "in_system_start": samples[0][2], "in_system_end": samples[-1][2],
            "in_system_growth_per_s": backlog_growth(
                [(t, n) for t, _, n in samples]),
            "sustained": sustained,
            "preemptions": sum(r.preempted for r in loop.records),
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else None,
            "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
            "output_tokens_per_s": tokens_in(loop.records, w0, w1)
            / args.seconds,
            # every SERIES_S from the start: [t, waiting, in system,
            # output tokens per second over the SERIES_S before t]
            "series": [[t, w, n, tokens_in(loop.records, t - SERIES_S, t)
                        / SERIES_S] for t, w, n in samples_all
                       if t % SERIES_S == 0]}), flush=True)
        loop.abort_open()
        if unsustained == STOP_AFTER:
            break


if __name__ == "__main__":
    main()
