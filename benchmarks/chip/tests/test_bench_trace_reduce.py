"""The reduction from profiler events to busy time, per-op time and idle
gaps: by hand on synthetic traces, on a small trace recorded on a v5e
(checked in beside this file), and the read of a real `.xplane.pb`."""

from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_by_hand():
    ms = 1_000_000
    events = {
        "devices": {"/device:TPU:0": [
            ["copy", 10 * ms, 20 * ms],        # 10-30
            ["fusion", 30 * ms, 5 * ms],       # 30-35, right after it
            ["kernel", 60 * ms, 20 * ms],      # 60-80
            ["kernel", 95 * ms, 20 * ms],      # 95-115, clipped at 100
        ]},
        "host": [["bench.window", 0, 100 * ms],
                 ["bench.step", 30 * ms, 40 * ms],     # 30-70
                 ["bench.wait", 80 * ms, 15 * ms],     # 80-95
                 ["bench.submit", 85 * ms, 2 * ms]],
    }
    out = tr.reduce(events)
    assert out["window_s"] == pytest.approx(0.1)
    # busy: 10-35, 60-80, 95-100 = 25 + 20 + 5 ms
    assert out["busy_s"] == pytest.approx(0.050)
    assert out["ops_s"] == pytest.approx(
        {"copy": 0.020, "fusion": 0.005, "kernel": 0.025})
    assert out["device_ops"][0] == ["kernel", pytest.approx(0.025)]
    # gaps: 0-10 (no span), 35-60 (step), 80-95 (wait: midpoint 87.5 is
    # past the submit span)
    assert out["idle_gaps"] == [["bench.step", pytest.approx(0.025)],
                                ["bench.wait", pytest.approx(0.015)],
                                ["none", pytest.approx(0.010)]]


def test_no_device_op_reads_nothing():
    events = {"devices": {"/device:TPU:0": []},
              "host": [["bench.window", 0, 10]]}
    assert tr.reduce(events) is None


def test_window_span_is_required():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": []})


def test_self_time_of_a_loop_excludes_its_body():
    events = {"devices": {"/device:TPU:0": [
        ["while.1", 0, 100], ["fusion.2", 10, 30], ["kernel.3", 50, 40]]},
        "host": [["bench.window", 0, 100]]}
    assert tr.reduce(events)["ops_s"] == pytest.approx(
        {"while.1": 30e-9, "fusion.2": 30e-9, "kernel.3": 40e-9})


def test_op_name_from_hlo_text():
    assert tr.op_name("%copy.55 = bf16[1,24,2978]{2,1,0:T(8,128)(2,1)} "
                      "copy(bf16[1,24,2978]{0,1,2} %p)") == \
        "copy.55 bf16[1,24,2978]"
    assert tr.op_name("%while.6 = (s32[], bf16[2]) while(...)") == "while.6"
    assert tr.op_name("jit_tick(123)") == "jit_tick(123)"


def test_recorded_v5e_trace():
    """0.4 s of Qwen1.5-0.5B serving on one v5e (three ticks, 5,985 op
    events), cut from a traced run of the chat cell."""
    out = tr.reduce(tr.load_events(str(DATA / "v5e_qwen_ticks.events.json")))
    assert out["window_s"] == pytest.approx(0.4)
    assert out["busy_s"] == pytest.approx(0.382166, abs=1e-6)
    # self times partition the busy time: no op is counted twice
    assert sum(out["ops_s"].values()) == pytest.approx(out["busy_s"])
    idle = sum(t for _, t in out["idle_gaps"])
    assert idle <= out["window_s"] - out["busy_s"] + 1e-9
    assert all(label == "bench.step" for label, _ in out["idle_gaps"])
    top = [name for name, _ in out["device_ops"][:4]]
    assert all("2978,16,2,16,64]" in name for name in top), top
    kernel = sum(t for n, t in out["ops_s"].items()
                 if n.startswith("paged_flash_attention"))
    assert 0.02 < kernel < 0.1


def test_read_xplane_finds_the_harness_spans(tmp_path):
    """A trace recorded here on the CPU: no TPU plane, and the harness's
    spans on the host plane."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = tr.read_xplane(str(path))
    assert events["devices"] == {}
    assert sorted(n for n, _, _ in events["host"]) == ["bench.step",
                                                        "bench.window"]
    assert tr.reduce(events) is None
