"""The tick path's phases and the tick program's scopes over one traced run
of a cell on the chip.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n> \
        --seconds <s> [--events PATH]

Runs the cell exactly as `run.py --trace 1` does and prints its result
line.  Then prints one more JSON line, read over the same traced
sub-window: host milliseconds per tick in each `tick.*` phase
(`TickLoop.phases`, `EngineStats.phases`), the host's own work and its
waits on the device per tick, the share of retirements that came late
(`TickLoop.retired_ready` / `retired_late`), device seconds per program
scope and the KV cache's share of them (`trace_phases.reduce`), the share
of `bench.step` that the program's spans cover, and the longest idle gaps
labelled by the innermost span.  `--events` writes the first
`EVENTS_S` seconds of the traced sub-window's events, a recorded trace for
the tests.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import run  # noqa: E402  (puts this directory on the import path)
import trace_phases  # noqa: E402

# the phases at the top of the tick path; the two waits nest inside
# tick.embed and tick.retire
WORK_PHASES = ("tick.schedule", "tick.prepare", "tick.stack", "tick.embed",
               "tick.sampling", "tick.dispatch", "tick.retire")
WAITS = ("tick.embed_wait", "tick.readback_wait")
KV_SCOPES = ("kv_slice", "kv_write", "kv_update")
EVENTS_S = 0.4


class PhaseWindow(run.SubWindow):
    """`run.SubWindow` that also reads the program's phase counters and
    retirement counts at its edges, and keeps the trace's events with the
    program's spans and scopes."""

    def _edge(self) -> dict:
        edge = super()._edge()
        loop = self.engine.loop
        edge["phases"] = {**loop.phases, **self.engine.backend.stats.phases}
        edge["retired"] = {"ready": loop.retired_ready,
                           "late": loop.retired_late}
        return edge

    def read(self):
        xplanes = sorted(self.dir.rglob("*.xplane.pb"))
        self.program_events = (trace_phases.read_xplane(str(xplanes[-1]))
                               if xplanes else None)
        return super().read()


def report(sub: PhaseWindow) -> Dict:
    """The phase split, waits, late retirements and program scopes of the
    traced sub-window, per tick."""
    a, b = sub.a, sub.b
    ticks = b["engine"]["ticks"] - a["engine"]["ticks"]
    ms = {k: (v - a["phases"].get(k, 0.0)) * 1e3 / ticks
          for k, v in sorted(b["phases"].items())} if ticks else {}
    ready = b["retired"]["ready"] - a["retired"]["ready"]
    late = b["retired"]["late"] - a["retired"]["late"]
    out = {"ticks": ticks, "phase_ms_per_tick": ms,
           "host_work_ms_per_tick": None, "host_wait_ms_per_tick": None,
           "retired_ready": ready, "retired_late": late,
           "late_retire_share": (100.0 * late / (ready + late)
                                 if ready + late else None),
           "kv_cache_ms_per_tick": None}
    if ms:
        wait = sum(ms.get(k, 0.0) for k in WAITS)
        out["host_wait_ms_per_tick"] = wait
        out["host_work_ms_per_tick"] = sum(
            ms.get(k, 0.0) for k in WORK_PHASES) - wait
    reduced: Optional[Dict] = (trace_phases.reduce(sub.program_events)
                               if sub.program_events else None)
    if reduced is not None:
        if ticks:
            out["kv_cache_ms_per_tick"] = sum(
                reduced["scopes_s"].get(s, 0.0) for s in KV_SCOPES) \
                * 1e3 / ticks
        out.update({k: reduced[k] for k in (
            "window_s", "busy_s", "scopes_s", "unscoped_ops",
            "program_share", "idle_gaps")})
    return out


def cut_events(events: Dict, seconds: float) -> Dict:
    """The events of the first `seconds` of the traced window, under a
    `bench.window` span of that length."""
    from trace_reduce import WINDOW_SPAN, window
    w0, _ = window(events)
    w1 = w0 + seconds * 1e9

    def inside(e):
        return e[1] < w1 and e[1] + e[2] > w0
    return {"devices": {p: [e for e in evs if inside(e)]
                        for p, evs in events["devices"].items()},
            "host": [e for e in events["host"]
                     if e[0] != WINDOW_SPAN and inside(e)]
            + [[WINDOW_SPAN, w0, w1 - w0]]}


def traced_run(cell, **kw):
    """`run.run_cell(cell, trace=True, **kw)` with a `PhaseWindow` as its
    sub-window; returns the result line's object and the window."""
    made = []

    def window(*args):
        made.append(PhaseWindow(*args))
        return made[-1]
    plain, run.SubWindow = run.SubWindow, window
    try:
        result = run.run_cell(cell, trace=True, **kw)
    finally:
        run.SubWindow = plain
    return result, made[0]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", default=None,
                    help="write the first EVENTS_S seconds of the traced "
                         "events here (JSON)")
    args = ap.parse_args(argv)
    cell = run.manifest.resolve(run.manifest.load(), args.workload)
    jax = run.configure_jax()
    devices = run.require_chips(jax, cell.chips)
    sys.path.insert(0, str(run.ROOT / "src"))
    result, sub = traced_run(cell, seed=args.seed, seconds=args.seconds,
                             devices=devices, jax=jax, t_start=T_START)
    print(json.dumps(result), flush=True)
    print(json.dumps(report(sub)), flush=True)
    if args.events and sub.program_events:
        from trace_reduce import save_events
        save_events(cut_events(sub.program_events, EVENTS_S), args.events)


if __name__ == "__main__":
    main()
