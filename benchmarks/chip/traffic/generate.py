"""Seeded open-loop traffic, read from a mix's parameter file by the
generator it names (`generators/<generator>.py`, whose `generate` returns
the schedule), and the helpers the generators share.

Adapted from the program's `data/workload.py` (ShareGPT-shaped lognormal
lengths, Poisson arrivals), so that a change to the program cannot move the
yardstick.  One change: every seed gets the same multisets of prompt
lengths, output lengths and inter-arrival gaps, taken at evenly spaced
quantiles of the distributions, and the seed only permutes them and draws
the token ids.  Runs with different seeds then carry the same work, in
another order (a generator may give the lead-in and the window a multiset
each, so that the window's work is the same too).
"""

from __future__ import annotations

import importlib.util
import math
import re
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np


@dataclass(frozen=True)
class Planned:
    """One request of the schedule: due `due_s` after the run's start."""

    index: int
    due_s: float
    prompt: List[int]
    max_new_tokens: int
    temperature: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: Dict, n: int) -> np.ndarray:
    """`n` lengths at evenly spaced quantiles of a lognormal with the given
    mean and sigma, clipped to [min, max]."""
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma ** 2 / 2.0
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    raw = np.exp(mu + sigma * z)
    return np.clip(raw, spec["min"], spec["max"]).astype(int)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """`n` inter-arrival gaps at evenly spaced quantiles of the exponential
    distribution of rate `rate`, scaled so that they sum to exactly
    `n / rate`."""
    gaps = -np.log1p(-_quantiles(n))
    return gaps * (n / rate) / gaps.sum()


GENERATORS = Path(__file__).resolve().parent / "generators"
NAME = re.compile(r"^[A-Za-z0-9_]{1,64}$")


def generator(name: str) -> Callable[..., List[Planned]]:
    """`generate` of `generators/<name>.py`."""
    path = GENERATORS / f"{name}.py"
    if not NAME.match(name) or not path.is_file():
        known = sorted(p.stem for p in GENERATORS.glob("*.py"))
        raise ValueError(f"unknown traffic generator {name!r}; known: "
                         f"{known}")
    spec = importlib.util.spec_from_file_location(
        f"chip_traffic_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def make_requests(params: Dict, *, seed: int, duration_s: float,
                  vocab: int) -> List[Planned]:
    """The schedule of the mix `params` over `duration_s` seconds, made by
    the generator the mix names."""
    return generator(params["generator"])(params, seed=seed,
                                          duration_s=duration_s, vocab=vocab)
