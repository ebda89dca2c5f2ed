"""The control: the plain reference put in the program's place at the next
lower precision (fp8) must come out not correct under the limits that the
program's own served tokens meet, on the same requests."""

import pytest

import correct
import run
from conftest import TINY_CONFIGS
from serve_loop import Record


@pytest.mark.parametrize("tiny_cell", sorted(TINY_CONFIGS), indirect=True)
def test_fp8_control_fails_where_the_program_passes(tiny_cell):
    from repro.serving import SamplingParams
    from traffic.generate import make_requests

    cfg, seed = tiny_cell.config, 2 ** 31 + 1
    limits = {k: cfg["check"][f"{k}_limit"]
              for k in ("logit_gap", "logprob_error")}
    server, params = run.build_server(tiny_cell, seed)
    # every request of the schedule, served to the end: the same work
    # however busy the CPU is
    records = [Record(p, f"c-{p.index}") for p in make_requests(
        tiny_cell.traffic, seed=seed, duration_s=1.5,
        vocab=cfg["vocab_size"])]
    for r in records:
        server.submit(r.planned.prompt, SamplingParams(
            max_new_tokens=r.planned.max_new_tokens), request_id=r.rid)
    server.drain()
    for r in records:
        out = server.get(r.rid)
        r.tokens, r.top_logprobs = out.token_ids, out.top_logprobs
        r.finish_reason = out.finish_reason
    picked = correct.sample(records, seed)
    run.free_server(server)
    model = tiny_cell.model
    program = correct.compare(model, cfg, params, picked)
    control = correct.compare(model, cfg, params, picked, control="fp8")
    assert correct.length_mismatches(picked) == 0
    assert program["tokens"] == control["tokens"] >= 64
    assert all(program[k] <= v for k, v in limits.items()), program
    assert any(control[k] > v for k, v in limits.items()), control
    # the verdict a run prints: the program correct, the control not
    verdict = {side: all(c["ok"] for c in run.compared(
        cfg, found, 0, 0).values()) for side, found in
        (("program", program), ("control", control))}
    assert verdict == {"program": True, "control": False}
