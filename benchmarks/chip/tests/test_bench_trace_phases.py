"""The program's spans and scopes read from a profiler trace
(`trace_phases`) and the phase report of a traced run (`phases.py`): by
hand on synthetic traces, on small traces recorded on a v5e (checked in
beside this file), and on the CPU at a tiny size."""

import json
import struct
from pathlib import Path

import jax
import pytest

import phases
import run
import trace_phases as tp
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_gap_inside_a_program_span_takes_its_label():
    events = {
        "devices": {"/device:TPU:0": [
            ["copy", 10 * MS, 20 * MS, "caches"],
            ["fusion", 60 * MS, 30 * MS, "jit(tick)/layers/kv_slice/ds"]]},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 30 * MS, 40 * MS],            # 30-70
                 ["tick.embed", 32 * MS, 20 * MS],            # 32-52
                 ["tick.embed_wait", 40 * MS, 12 * MS]],      # 40-52
    }
    out = tp.reduce(events)
    # gaps: 30-60 (midpoint 45: the wait), 0-10 (none), 90-100 (none)
    assert out["idle_gaps"][0] == ["tick.embed_wait", pytest.approx(0.030)]
    # a gap no program span holds keeps the harness's label
    events["host"] = events["host"][:2]
    assert tp.reduce(events)["idle_gaps"][0] == ["bench.step",
                                                 pytest.approx(0.030)]


def test_scopes_take_the_innermost_known_name():
    assert tp.scope_of("jit(tick)/layers/while/body/closed_call/kv_slice/"
                       "dynamic_slice") == "kv_slice"
    assert tp.scope_of("jit(tick)/layers/while") == "layers"
    assert tp.scope_of("jit(tick)/layers/while/body/closed_call/attention/"
                       "jit(paged_flash_attention)/pallas_call") == "attention"
    assert tp.scope_of("caches['b0_attn_mlp']['kv']") == tp.UNSCOPED
    assert tp.scope_of("") == tp.UNSCOPED


def test_scopes_s_by_hand():
    events = {"devices": {"/device:TPU:0": [
        ["while.1", 0, 100, "jit(tick)/layers/while"],
        ["fusion.2", 10, 30, "jit(tick)/layers/while/body/kv_slice/x"],
        ["kernel.3", 50, 40, "jit(tick)/layers/while/body/attention/y"],
        ["copy.4", 100, 20, "caches"]]},
        "host": [["bench.window", 0, 120]]}
    out = tp.reduce(events)
    assert out["scopes_s"] == pytest.approx(
        {"layers": 30e-9, "kv_slice": 30e-9, "attention": 40e-9,
         tp.UNSCOPED: 20e-9})
    assert out["unscoped_ops"] == [["copy.4 caches", pytest.approx(20e-9)]]


def test_program_share_by_hand():
    events = {"devices": {"/device:TPU:0": [["op", 0, 10 * MS]]},
              "host": [["bench.window", 0, 100 * MS],
                       ["bench.step", 10 * MS, 40 * MS],      # 10-50
                       ["tick.schedule", 10 * MS, 10 * MS],   # 10-20
                       ["tick.embed", 20 * MS, 20 * MS],      # 20-40
                       ["tick.embed_wait", 25 * MS, 10 * MS],
                       ["bench.step", 60 * MS, 40 * MS],      # 60-100
                       ["tick.retire", 90 * MS, 20 * MS]]}    # 90-110
    # covered 10-40 and 90-100 of 80 ms of steps
    assert tp.reduce(events)["program_share"] == pytest.approx(40 / 80)


def test_old_recording_reduces_as_trace_reduce_does():
    """Events with no program span and no scope path: every number
    `trace_reduce` gives comes out the same, to the byte."""
    events = tr.load_events(str(DATA / "v5e_qwen_ticks.events.json"))
    old, new = tr.reduce(events), tp.reduce(events)
    assert json.dumps(old) == json.dumps({k: new[k] for k in old})
    assert new["scopes_s"] == {tp.UNSCOPED: pytest.approx(old["busy_s"])}


def test_recorded_v5e_trace_with_scopes():
    """0.4 s of Qwen1.5-0.5B serving on one v5e with the program's spans
    and scopes, cut from a traced run of the chat cell (`phases.py
    --events`)."""
    events = tr.load_events(str(DATA / "v5e_qwen_scoped_ticks.events.json"))
    out = tp.reduce(events)
    assert out["window_s"] == pytest.approx(phases.EVENTS_S)
    # scopes partition the device time
    assert sum(out["scopes_s"].values()) == pytest.approx(out["busy_s"])
    assert {"layers", "qkv", "kv_slice", "kv_write", "attention",
            "kv_update", "mlp", "head", "sample"} <= set(out["scopes_s"])
    kernel = sum(t for n, t in out["ops_s"].items()
                 if n.startswith("paged_flash_attention"))
    assert out["scopes_s"]["attention"] >= kernel > 0
    # every op of 1% or more of the device time has a scope but the copy
    # of the KV pool argument the compiler puts before the tick
    for name, t in out["unscoped_ops"]:
        assert t < 0.01 * out["busy_s"] or "caches['" in name, name
    assert all(label.startswith("tick.") for label, _ in out["idle_gaps"]
               if label != "none")
    assert out["program_share"] > 0.9


def _pb(*fields) -> bytes:
    """Encode a protobuf message from (number, value) fields: ints as
    varints, floats as fixed 64-bit, str and bytes length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        elif isinstance(v, float):
            out += varint(num << 3 | 1) + struct.pack("<d", v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_paths_from_event_metadata(tmp_path):
    """The `tf_op` stat of each TPU event's metadata, as a string or as a
    reference to a stat metadata name; other stats and planes ignored."""
    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_meta(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name),
                                       *[(5, st) for st in stats]))))
    tpu = _pb((1, 7), (2, "/device:TPU:0"), stat_meta(3, "tf_op"),
              stat_meta(4, "jit(tick)/mlp/dot_general:"),
              stat_meta(9, "flops"),
              event_meta(1, "%a = f32[2] add()",
                         _pb((1, 9), (2, 1.5)),
                         _pb((1, 3), (5, "jit(tick)/layers/kv_slice/x:"))),
              event_meta(2, "%b = f32[2] dot()", _pb((1, 3), (7, 4))),
              event_meta(5, "%c = f32[2] copy()", _pb((1, 9), (3, 12))))
    host = _pb((1, 8), (2, "/host:CPU"), stat_meta(3, "tf_op"),
               event_meta(1, "tick.stack", _pb((1, 3), (5, "host:"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, tpu), (1, host)))
    assert tp.op_paths(str(path)) == {
        "%a = f32[2] add()": "jit(tick)/layers/kv_slice/x",
        "%b = f32[2] dot()": "jit(tick)/mlp/dot_general"}


def test_read_xplane_keeps_program_spans(tmp_path):
    """A trace recorded here on the CPU: no TPU plane; the harness's and
    the program's spans on the host plane, nothing else."""
    import jax.numpy as jnp

    from repro.runtime.core import Phases

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    ph = Phases()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            with ph.span("tick.dispatch"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("other"):
                pass
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = tp.read_xplane(str(path))
    assert events["devices"] == {}
    assert sorted(n for n, _, _ in events["host"]) == [
        "bench.step", "bench.window", "tick.dispatch"]
    assert tp.reduce(events) is None


def test_phase_report_of_a_traced_run(tiny_cell, monkeypatch):
    """`phases.py`'s report over a traced run at a tiny size on the CPU:
    the program's counters read at the sub-window's edges (the CPU trace
    has no device plane, so nothing device-side is read)."""
    monkeypatch.setattr(run, "MIN_COMPARED_TOKENS", 1)
    monkeypatch.setattr(run, "peaks_for", lambda kind: {})  # no CPU peaks
    result, sub = phases.traced_run(tiny_cell, seed=2 ** 31 + 5, seconds=4.0,
                                    devices=jax.devices(), jax=jax)
    assert result["correct"], result["checks"]
    assert run.SubWindow.__name__ == "SubWindow"     # put back
    out = phases.report(sub)
    assert out["ticks"] > 0
    assert set(phases.WORK_PHASES) | set(phases.WAITS) <= set(
        out["phase_ms_per_tick"])
    assert 0 <= out["host_work_ms_per_tick"]
    assert 0 <= out["host_wait_ms_per_tick"]
    assert out["retired_ready"] + out["retired_late"] > 0
    assert 0 <= out["late_retire_share"] <= 100
    assert out["kv_cache_ms_per_tick"] is None
