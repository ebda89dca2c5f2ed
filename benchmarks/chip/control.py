"""Readings that set a cell's `logit_gap_limit`, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 20

For each seed the server of the cell, holding that seed's weights, serves
the cell's traffic for the lead-in and a window of `--seconds`, exactly as
a benchmark run does, and the requests the window finished are sampled by
the same rule.  Once every seed has been served the server is freed, and
for each seed the sample is compared with the plain reference: the
program's served tokens (the lower reading), and the reference put in the
program's place at fp8, the control (the upper reading).  Each is also
judged by `run.compared` under the limits in the configuration file, the
verdict a benchmark run would print.  Prints one JSON line per seed and a
summary line.  The benchmark's own runs
never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (puts this directory on the import path)

CONTROL = "fp8"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = run.manifest.resolve(run.manifest.load(), args.workload)
    jax = run.configure_jax()
    devices = run.require_chips(jax, cell.chips)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.serving import SamplingParams

    import correct
    from serve_loop import OpenLoop, attach_logprobs
    from traffic.generate import make_requests

    cfg, traffic = cell.config, cell.traffic
    server, params = run.build_server(cell, seeds[0])
    engine = server.replicas[0]
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), params)
    del params
    run.warm_up(server, cfg["vocab_size"])
    run.log(f"set-up {time.perf_counter() - T_START:.3f}s")
    w1 = float(traffic["lead_in_s"]) + args.seconds
    picked = {}
    for seed in seeds:
        engine.params = engine.backend.params = None
        engine.params = engine.backend.params = \
            cell.model.make_program_params(cfg, seed, like)
        plan = make_requests(traffic, seed=seed, duration_s=w1,
                             vocab=cfg["vocab_size"])
        loop = OpenLoop(server, plan, SamplingParams, tag=f"s{seed}")
        loop.run(time.perf_counter(), w1)
        picked[seed] = correct.sample(
            [r for r in loop.records
             if r.finish_s is not None and r.finish_s <= w1], seed)
        attach_logprobs(server, picked[seed])
        loop.abort_open()
        run.log(f"seed {seed}: served, {len(picked[seed])} requests sampled")
    run.free_server(server)
    del server, engine
    gc.collect()
    rows = []
    for seed in seeds:
        params = cell.model.make_program_params(cfg, seed, like)
        mismatches = correct.length_mismatches(picked[seed])
        row = {"seed": seed, "length_mismatches": mismatches}
        for side, control in (("program", None), (CONTROL, CONTROL)):
            found = correct.compare(cell.model, cfg, params, picked[seed],
                                    control=control)
            checks = run.compared(cfg, found, mismatches, 0)
            row[side] = dict(found, correct=all(
                c["ok"] for c in checks.values()))
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params
    summary = {"workload": args.workload, "seeds": len(rows),
               "device": devices[0].device_kind}
    for k in ("logit_gap", "logprob_error"):
        lower = max(r["program"][k] for r in rows)
        upper = min(r[CONTROL][k] for r in rows)
        summary[k] = {"lower": lower, "upper": upper,
                      "ratio": upper / lower if lower else None}
    for side in ("program", CONTROL):
        summary[f"{side}_correct"] = [r[side]["correct"] for r in rows]
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
