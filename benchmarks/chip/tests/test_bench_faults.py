"""The harness end to end on the CPU at a tiny size, through the program's
chip-mode build, with the chip check skipped: a sound run comes out
correct, and a timed path broken underneath comes out not correct.

How many requests a short window finishes here depends on how busy the
CPU is, so these runs ask for at least one compared token instead of the
benchmark's 64."""

import jax
import jax.numpy as jnp
import pytest

import run
from conftest import TINY_CONFIGS

# every test here runs over each tiny configuration
pytestmark = pytest.mark.parametrize("tiny_cell", sorted(TINY_CONFIGS),
                                     indirect=True)


@pytest.fixture(autouse=True)
def _any_compared_token(monkeypatch):
    monkeypatch.setattr(run, "MIN_COMPARED_TOKENS", 1)


def _run(cell, seed=2 ** 31 + 17):
    return run.run_cell(cell, seed=seed, seconds=4.0, trace=False,
                        devices=jax.devices(), jax=jax)


def _break_ticks(monkeypatch, wrap):
    """Route every tick program of the server `run` builds through
    `wrap(tick)`."""
    build = run.build_server

    def broken_build(cell, seed):
        server, params = build(cell, seed)
        backend = server.replicas[0].backend
        get = backend._get_tick
        backend._get_tick = lambda bucket: wrap(get(bucket))
        return server, params
    monkeypatch.setattr(run, "build_server", broken_build)


def test_sound_run_is_correct(tiny_cell):
    out = _run(tiny_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m.name for m in tiny_cell.end_to_end}
    assert list(out)[-1] == "checks"


def test_altered_token_is_caught(tiny_cell, monkeypatch):
    vocab = tiny_cell.config["vocab_size"]

    def wrap(tick):
        def altered(*args):
            carry, caches, tokens, top = tick(*args)
            return carry, caches, jnp.where(
                tokens >= 0, (tokens + 1) % vocab, tokens), top
        return altered
    _break_ticks(monkeypatch, wrap)
    out = _run(tiny_cell)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_state_left_unchanged_is_caught(tiny_cell, monkeypatch):
    def wrap(tick):
        def unchanged(params, caches, *rest):
            carry, _, tokens, top = tick(
                params, jax.tree.map(jnp.copy, caches), *rest)
            return carry, caches, tokens, top
        return unchanged
    _break_ticks(monkeypatch, wrap)
    out = _run(tiny_cell)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]
