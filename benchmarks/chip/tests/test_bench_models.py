"""Model modules: found by the name a configuration gives, used for the
weights, the check of the program and the reference, and the dense decoder
reading exactly what the harness read before it moved into
`models/dense_decoder.py`."""

import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import correct
import manifest
import run
from conftest import TINY_CONFIG, TINY_CONFIGS, TINY_TRAFFIC, tiny_arch
from serve_loop import BatchCount

DATA = manifest.HERE / "tests" / "data"

TOY = '''
import jax
import jax.numpy as jnp
import numpy as np

import weights

CALLS = []


def check_program(cfg, server):
    CALLS.append("check_program")


def make_program_params(cfg, seed, program_like):
    CALLS.append("make_program_params")
    return weights.place(lambda key, like: jax.tree.map(
        lambda s: jnp.full(s.shape, 0.5, s.dtype), like), seed, program_like)


def teacher_forced_logits(cfg, params, prompt, served, *, low=None):
    """The served token leads every other by 1."""
    CALLS.append(("teacher_forced_logits", low))
    logits = np.zeros((len(served), cfg["vocab_size"]), np.float32)
    logits[np.arange(len(served)), served] = 1.0
    return logits


def step_flops(cfg, batches):
    return 0.0


def attention_cost(cfg, batches):
    return {"flops": 0.0, "bytes": 0.0}
'''


def _checkout(root, model, models):
    """A checkout at `root` with one cell, `toy.chat`, whose configuration
    names `model`, and `models` (name -> source) under `models/`."""
    bench = root / manifest.BENCH_DIR
    for sub in ("configs", "traffic", "models"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps(
        dict(TINY_CONFIG, model=model)))
    (bench / "traffic" / "toy-chat.json").write_text(json.dumps(TINY_TRAFFIC))
    for name, source in models.items():
        (bench / "models" / f"{name}.py").write_text(source)
    return {"configs": [{"name": "toy",
                         "file": f"{manifest.BENCH_DIR}/configs/toy.json"}],
            "workloads": [{"name": "toy.chat", "config": "toy",
                           "traffic": "toy-chat", "chips": 1}],
            "end_to_end": [], "per_layer": []}


def test_model_module_in_a_new_file_is_found_and_used(tmp_path, monkeypatch):
    import repro.configs
    from serve_loop import Record
    from traffic.generate import Planned

    bench = _checkout(tmp_path, "toy", {"toy": TOY})
    cell = manifest.resolve(bench, "toy.chat", root=tmp_path)
    toy = cell.model
    assert toy.__file__ == str(tmp_path / manifest.BENCH_DIR / "models"
                               / "toy.py")
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: tiny_arch())
    server, params = run.build_server(cell, seed=2 ** 31 + 7)
    engine = server.replicas[0]
    assert engine.params is params and engine.backend.params is params
    run.free_server(server)
    assert all(bool(jnp.all(x == 0.5)) for x in jax.tree.leaves(params))
    assert toy.CALLS == ["check_program", "make_program_params"]

    prompt, served = [3, 1, 4], [1, 5, 9, 2]
    best = 1.0 - np.log(np.e + TINY_CONFIG["vocab_size"] - 1)
    rec = Record(Planned(0, 0.0, prompt, len(served), 0.0), "r0",
                 tokens=served, top_logprobs=[(best, 0.0)] * len(served))
    found = correct.compare(toy, cell.config, params, [rec])
    assert found["logit_gap"] == 0.0 and found["tokens"] == 4
    assert found["logprob_error"] < 1e-5
    correct.compare(toy, cell.config, params, [rec], control="fp8")
    assert toy.CALLS[2:] == [("teacher_forced_logits", None),
                             ("teacher_forced_logits", None),
                             ("teacher_forced_logits", "fp8")]


def test_unknown_model_fails_at_resolve_naming_the_known(tmp_path):
    bench = _checkout(tmp_path, "no-such-model", {})
    shutil.rmtree(tmp_path / manifest.BENCH_DIR / "models")
    shutil.copytree(manifest.HERE / "models",
                    tmp_path / manifest.BENCH_DIR / "models")
    with pytest.raises(KeyError, match="no-such-model.*dense_decoder"):
        manifest.resolve(bench, "toy.chat", root=tmp_path)


def test_module_without_an_interface_function_fails(tmp_path):
    bench = _checkout(tmp_path, "half", {"half": TOY.split(
        "def step_flops")[0]})
    with pytest.raises(AttributeError, match="step_flops.*attention_cost"):
        manifest.resolve(bench, "toy.chat", root=tmp_path)


@pytest.mark.parametrize("config", [c["name"] for c in
                                    manifest.load()["configs"]])
def test_every_configuration_names_a_whole_module(config):
    bench = manifest.load()
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == config)
    resolved = manifest.resolve(bench, cell)
    model = resolved.model
    assert model.__file__ == str(manifest.HERE / "models"
                                 / f"{resolved.config['model']}.py")
    for f in manifest.MODEL_FUNCTIONS:
        assert callable(getattr(model, f)), (config, f)


@pytest.mark.parametrize("tiny_cell", sorted(TINY_CONFIGS), indirect=True)
def test_dense_decoder_refuses_a_program_other_than_the_file(tiny_cell):
    from repro.serving import EngineSpec, ServeSpec, build

    cfg, dense = tiny_cell.config, tiny_cell.model
    server = build(ServeSpec(backend="engine",
                             engine=EngineSpec(**cfg["engine"])))
    try:
        dense.check_program(cfg, server)
        for key, value in (("num_key_value_heads", 1), ("head_dim", 32),
                           ("qkv_bias", not cfg["qkv_bias"])):
            with pytest.raises(ValueError, match=key):
                dense.check_program(dict(cfg, **{key: value}), server)
    finally:
        server.close()


# ---- the dense decoder reads what the harness read before the move

@pytest.fixture(scope="module")
def parent():
    with open(DATA / "dense_decoder_parent.json") as f:
        recorded = json.load(f)
    return recorded, np.load(DATA / "dense_decoder_parent_logits.npz")


SIZES = ("tiny", "qwen1.5-0.5b-2l")


def _program_like(cfg):
    """The program's parameter shapes for `cfg`, bf16, on the CPU."""
    from repro.models.transformer import param_shapes
    sharding = SingleDeviceSharding(jax.devices()[0])
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding),
        param_shapes(tiny_arch(cfg)), is_leaf=lambda x: isinstance(x, tuple))


def _dense():
    return manifest.model_module("dense_decoder")


@pytest.mark.parametrize("size", SIZES)
def test_dense_decoder_weights_are_the_parents_bit_for_bit(parent, size):
    recorded, _ = parent
    entry = recorded["sizes"][size]
    like = _program_like(entry["config"])
    for seed in recorded["seeds"]:
        params = _dense().make_program_params(entry["config"], seed, like)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        hashes = {jax.tree_util.keystr(p): hashlib.sha256(
            np.asarray(x).tobytes()).hexdigest() for p, x in flat}
        assert hashes == entry["hashes"][str(seed)], (size, seed)


@pytest.mark.parametrize("size", SIZES)
def test_dense_decoder_counts_are_the_parents(parent, size):
    recorded, _ = parent
    entry, dense = recorded["sizes"][size], _dense()
    cfg = entry["config"]
    batches = [BatchCount(*b) for b in recorded["batches"]]
    assert dense.layer_matmul_params(cfg) == entry["layer_matmul_params"]
    assert [dense.step_flops(cfg, [b]) for b in batches] + [
        dense.step_flops(cfg, batches)] == entry["step_flops"]
    assert [dense.attention_cost(cfg, [b]) for b in batches] + [
        dense.attention_cost(cfg, batches)] == entry["attention_cost"]


@pytest.mark.parametrize("size", SIZES)
def test_dense_decoder_reference_logits_are_the_parents(parent, size):
    recorded, logits = parent
    entry = recorded["sizes"][size]
    cfg = entry["config"]
    params = _dense().make_program_params(cfg, recorded["seeds"][0],
                                          _program_like(cfg))
    for low in (None, "fp8"):
        got = _dense().teacher_forced_logits(
            cfg, params, entry["prompt"], entry["served"], low=low)
        key = f"{size}.{low or 'f32'}"
        want = logits[f"{key}.cols"]
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(got[:, ::entry["logit_stride"]], want,
                                   rtol=1e-6, atol=1e-6 * scale, err_msg=key)
        np.testing.assert_allclose(got.max(axis=1), logits[f"{key}.max"],
                                   rtol=1e-6, atol=1e-6 * scale, err_msg=key)
        assert (got.argmax(axis=1) == logits[f"{key}.argmax"]).all(), key
