"""`BENCHMARK.json` resolves by name: every cell to its configuration and
traffic file, every metric to a reader, and names and units keep to the
allowed characters."""

import json
import re

import pytest

import manifest
from traffic.generate import generator

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_every_cell_resolves(bench):
    assert bench["paths"] == [str(manifest.BENCH_DIR)]
    for w in bench["workloads"]:
        cell = manifest.resolve(bench, w["name"])
        assert cell.chips in (1, 4)
        assert callable(generator(cell.traffic["generator"]))
        for key in ("hidden_size", "num_hidden_layers", "vocab_size",
                    "torch_dtype", "engine", "check"):
            assert key in cell.config, (cell.config["name"], key)
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.reader(m.name))


def test_configs_are_used_and_files_unique(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(manifest.ROOT / c["file"]) as f:
            body = json.load(f)
        departures = [k for k in body.get("departures", {}) if k != "about"]
        assert sorted(c["reduced"]) == sorted(list(body["reduced"])
                                              + departures)


def test_names_and_units(bench):
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_reader_found_by_base_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "probe.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    read = manifest.reader("probe.batch", here=tmp_path)
    assert read({"x": 21}) == 42
    with pytest.raises(FileNotFoundError):
        manifest.reader("absent.chat", here=tmp_path)


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(KeyError):
        manifest.resolve(bench, "no-such-cell")
