"""Poisson arrivals with lognormal prompt and output lengths (ShareGPT-
shaped chat), the seed arranging fixed multisets of lengths and gaps.

The lead-in (`lead_in_s`, when the mix has one) and the rest of the
schedule, the measured window, each get multisets of their own, so every
seed puts the same requests into the window, in another order.  The
lead-in is arranged alike for every seed (by `lead_in_seed`), so every
window starts from the same load; only its token ids follow the seed.
With `block_requests` the window is cut into runs of about that many
consecutive requests, and each run takes gaps, prompts and outputs from
every stratum of their sorted multisets: short, middling and long alike.

Parameters: `rate_per_s`; `prompt` and `output`, each with `mean`, `sigma`,
`min` and `max`; `lead_in_s` and `lead_in_seed` (default 0); `block_requests`
(default 0: one run); `temperature` (default 0, greedy).
"""

from typing import Dict, List

import numpy as np

from traffic.generate import Planned, lognormal_lengths, poisson_gaps


def arrange(values: np.ndarray, rng, blocks: int) -> np.ndarray:
    """`values` in an order drawn from `rng`.  With `blocks` > 1 the order
    is cut into that many runs of near equal size, and the sorted values are
    dealt out `blocks` neighbours at a time, each to another run."""
    n = len(values)
    if blocks <= 1:
        return rng.permutation(values)
    room = [n // blocks + (b < n % blocks) for b in range(blocks)]
    held: List[list] = [[] for _ in range(blocks)]
    ranked = np.sort(values)
    for j in range(0, n, blocks):
        chunk = rng.permutation(ranked[j:j + blocks])
        order = sorted(range(blocks), key=lambda b: (-room[b], rng.random()))
        for x, b in zip(chunk, order):
            held[b].append(x)
            room[b] -= 1
    return np.concatenate([rng.permutation(h) for h in held])


def _part(params: Dict, rng, ids, start: float, length: float, first: int,
          blocks_of: int, vocab: int) -> List[Planned]:
    """Requests due in [start, start + length): round(rate x length) of
    them, the first due at `start`, the gaps scaled to span `length`; their
    order drawn from `rng`, their token ids from `ids`."""
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * length)))
    blocks = n // blocks_of if blocks_of > 0 else 1
    gaps = arrange(poisson_gaps(rate, n), rng, blocks)
    gaps *= length / gaps.sum()
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = arrange(lognormal_lengths(params["prompt"], n), rng, blocks)
    outputs = arrange(lognormal_lengths(params["output"], n), rng, blocks)
    temperature = float(params.get("temperature", 0.0))
    return [Planned(first + i, float(due[i]),
                    ids.integers(0, vocab, int(prompts[i])).tolist(),
                    int(outputs[i]), temperature)
            for i in range(n)]


def generate(params: Dict, *, seed: int, duration_s: float,
             vocab: int) -> List[Planned]:
    """Poisson arrivals at `params["rate_per_s"]` over `duration_s`, each
    with a lognormal prompt and output length."""
    rng = np.random.default_rng(seed)
    lead = min(float(params.get("lead_in_s", 0.0)), duration_s)
    plan = []
    if lead > 0:
        fixed = np.random.default_rng(int(params.get("lead_in_seed", 0)))
        plan = _part(params, fixed, rng, 0.0, lead, 0, 0, vocab)
    if duration_s > lead:
        plan += _part(params, rng, rng, lead, duration_s - lead, len(plan),
                      int(params.get("block_requests", 0)), vocab)
    return plan
