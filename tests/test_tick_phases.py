"""Spans and counters of the tick path (`Phases`, `TickLoop`, `EngineStats`)
and the named scopes of the tick program.

Every phase of a tick is timed on the host clock under a fixed name, and
the host's two waits on the device are counted under their own names,
nested inside the phases that hold them: `tick.embed_wait` inside
`tick.embed`, `tick.readback_wait` inside `tick.retire`.  Each retired
batch is counted once, as retired before the next schedule
(`retired_ready`) or after it (`retired_late`).
"""

import dataclasses
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, make_reduced
from repro.core import SamplingParams, ThrottleConfig
from repro.models import transformer as tfm
from repro.models.serve import ServeDims
from repro.runtime.core import Phases
from repro.runtime.engine import EngineStats, PipelineEngine

LOOP_PHASES = {"tick.schedule", "tick.prepare", "tick.retire"}
BACKEND_PHASES = {"tick.stack", "tick.embed", "tick.sampling",
                  "tick.dispatch"}
WAITS = {"tick.embed_wait", "tick.readback_wait"}
SCOPES = ("layers", "qkv", "kv_slice", "kv_write", "attention", "kv_update",
          "mlp", "head", "sample")
DELAY_S = 0.04


def build(async_dispatch: bool) -> PipelineEngine:
    """The chip mode's shape at a reduced width: one stage whose layers run
    in the layer scan."""
    cfg = make_reduced(get_config("qwen1.5-0.5b")).on_stages(1)
    cfg = dataclasses.replace(cfg, dtype="float32")
    mesh = jax.make_mesh((1, 1, 1), ("data", "stage", "tensor"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    dims = ServeDims(Sp=1, C=16, Sd=8, pages=128, page=8, Bp=16, Bd=16,
                     slots=16, Te=0)
    with jax.set_mesh(mesh):
        params = tfm.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, tfm.param_pspecs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        th = ThrottleConfig(pipeline_depth=1, max_prefill_tokens=16,
                            min_prefill_tokens=4, num_iters_T=2)
        return PipelineEngine(cfg, dims, params, mesh, th,
                              async_dispatch=async_dispatch)


def serve(eng: PipelineEngine, lengths=(7, 23, 12), new: int = 5) -> None:
    rng = np.random.default_rng(3)
    reqs = [eng.add_request(list(rng.integers(0, eng.cfg.vocab_size, n)),
                            SamplingParams(max_new_tokens=new))
            for n in lengths]
    eng.step()
    eng.drain(max_ticks=500)
    assert all(r.is_finished for r in reqs)


def count_retirements(eng: PipelineEngine) -> list:
    """Wrap the loop's retirement; returns the list of retired batch ids."""
    retired = []
    inner = eng.loop._retire

    def counted(batch_id, tokens, now):
        retired.append(batch_id)
        return inner(batch_id, tokens, now)
    eng.loop._retire = counted
    return retired


class SlowArray:
    """A device array whose host copy takes `DELAY_S` longer: a stand-in
    for a device that is still busy."""

    def __init__(self, a) -> None:
        self.a = a

    def __array__(self, dtype=None, copy=None):
        time.sleep(DELAY_S)
        return np.asarray(self.a, dtype)


def slow_device(eng: PipelineEngine) -> None:
    """Make every embedding and token readback of `eng` wait `DELAY_S`."""
    be = eng.backend
    embed, get_tick = be._embed, be._get_tick
    be._embed = lambda p, t: SlowArray(embed(p, t))

    def slow_tick(bucket):
        fn = get_tick(bucket)

        def run(*args):
            carry, caches, tokens, top_lp = fn(*args)
            return carry, caches, SlowArray(tokens), top_lp
        return run
    be._get_tick = slow_tick


def test_phases_time_and_nest():
    ph = Phases()
    with ph.span("outer"):
        with ph.span("inner"):
            time.sleep(0.01)
    with ph.span("inner"):
        pass
    assert set(ph) == {"outer", "inner"}
    assert ph["outer"] >= 0.01
    assert ph["inner"] >= 0.01


@pytest.mark.parametrize("async_dispatch", [False, True],
                         ids=["sync", "async"])
def test_phase_counters_within_wall_time(async_dispatch):
    eng = build(async_dispatch)
    retired = count_retirements(eng)
    t0 = time.perf_counter()
    serve(eng)
    wall = time.perf_counter() - t0
    loop, be = eng.loop.phases, eng.stats.phases
    assert set(loop) == LOOP_PHASES
    assert set(be) == BACKEND_PHASES | WAITS
    assert all(v >= 0 for v in [*loop.values(), *be.values()])
    top = sum(loop.values()) + sum(be[k] for k in BACKEND_PHASES)
    assert top <= wall
    # the waits are inside the phases that hold them
    assert be["tick.embed_wait"] <= be["tick.embed"]
    assert be["tick.readback_wait"] <= loop["tick.retire"]
    assert retired
    assert eng.loop.retired_ready + eng.loop.retired_late == len(retired)
    if not async_dispatch:
        assert eng.loop.retired_late == 0


@pytest.mark.parametrize("async_dispatch", [False, True],
                         ids=["sync", "async"])
def test_waits_counted_under_wait_names(async_dispatch):
    """A device that makes the host wait shows up in the two wait counters
    and in no phase of host work."""
    eng = build(async_dispatch)
    serve(eng)                      # compile outside the measured serve
    eng.loop.phases.clear()
    eng.stats.phases.clear()
    eng.loop.retired_ready = eng.loop.retired_late = 0
    slow_device(eng)
    retired = count_retirements(eng)
    embeds = []
    embed = eng.backend._embed
    eng.backend._embed = lambda p, t: embeds.append(1) or embed(p, t)
    serve(eng)
    loop, be = eng.loop.phases, eng.stats.phases
    assert be["tick.embed_wait"] >= DELAY_S * len(embeds)
    assert be["tick.readback_wait"] >= DELAY_S * len(retired)
    work = (sum(loop.values()) + sum(be[k] for k in BACKEND_PHASES)
            - be["tick.embed_wait"] - be["tick.readback_wait"])
    injected = DELAY_S * (len(embeds) + len(retired))
    assert work < injected / 2
    # a readback that never reports ready retires late in async mode
    assert eng.loop.retired_ready + eng.loop.retired_late == len(retired)
    if async_dispatch:
        assert eng.loop.retired_late == len(retired)


def test_engine_stats_have_no_device_s():
    assert "device_s" not in {f.name for f in dataclasses.fields(EngineStats)}
    assert not hasattr(EngineStats(), "device_s")


def scopes_of(lowered) -> set:
    """Every scope in the name paths of a lowered program's locations (a
    scan body's paths are relative to the scan)."""
    paths = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    return {part for path in paths for part in path.split("/")[:-1]}


def test_tick_program_carries_scopes():
    be = build(async_dispatch=False).backend
    assert set(SCOPES) <= scopes_of(be.lower_tick())
    tokens = jnp.zeros((1, 1), jnp.int32)
    assert "embed" in scopes_of(be._embed.lower(be.params, tokens))


def test_spans_reach_the_profiler(tmp_path):
    """The phases are host spans of the profiler's trace, on the plane
    that holds the device's ops."""
    from jax.profiler import ProfileData
    eng = build(async_dispatch=True)
    serve(eng)
    with jax.profiler.trace(str(tmp_path)):
        serve(eng)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert LOOP_PHASES | BACKEND_PHASES | WAITS <= names
