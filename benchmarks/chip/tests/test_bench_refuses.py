"""`run.py` exits non-zero and prints no result without a TPU, and in a
directory that holds only `BENCHMARK.json` and the benchmark's files."""

import os
import shutil
import subprocess
import sys

import manifest

ROOT = manifest.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    cell = manifest.load()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in manifest.load()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(
                            "__pycache__", ".jax_cache", ".out"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
