"""Whether what the timed path served is right: a sample of the requests
the window finished, each compared with the plain reference.

Two numbers are compared, each the widest over every served token of the
sample:
- logit_gap: by how much the reference's logit of the served token lies
  below the reference's best logit at that position.  Greedy decoding serves
  the program's own best token, so the gap is 0 wherever the program and the
  reference agree, and grows with how far the program's logits are off where
  they disagree.
- logprob_error: how far the log-prob the server reported for the served
  token (the largest of the two it returns) lies from the reference's
  log-prob of that token.  It reads the program's numerical error at every
  token, not only at near-ties.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import reference

SAMPLE_REQUESTS = 8


def sample(records: Sequence, seed: int, k: int = SAMPLE_REQUESTS) -> List:
    """Up to `k` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    done = sorted((r for r in records if r.finish_reason is not None),
                  key=lambda r: r.planned.index)
    if len(done) <= k:
        return done
    longest = max(done, key=lambda r: len(r.planned.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    picked = rng.choice(len(rest), size=k - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(picked)]


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of the
    token chosen there."""
    chosen = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(axis=1) - chosen


def log_softmax(logits: np.ndarray) -> np.ndarray:
    m = logits.max(axis=1, keepdims=True)
    return logits - m - np.log(np.exp(logits - m).sum(axis=1, keepdims=True))


def compare(cfg: Dict, params: Dict, picked: Sequence, *,
            control=None) -> Dict:
    """Both numbers over every served token of `picked`.  With `control`
    ("fp8") the reference at that precision stands in for the
    program: at each position of the same prompts and served tokens its own
    best token, and its log-prob of it, are judged in place of the served
    ones."""
    gap = err = 0.0
    count = 0
    for rec in picked:
        ref = reference.teacher_forced_logits(cfg, params,
                                              rec.planned.prompt, rec.tokens)
        if control:
            low = log_softmax(reference.teacher_forced_logits(
                cfg, params, rec.planned.prompt, rec.tokens, low=control))
            tokens = low.argmax(axis=1)
            reported = low.max(axis=1)
        else:
            tokens = np.asarray(rec.tokens)
            reported = np.array([lp[0] for lp in rec.top_logprobs])
        idx = np.arange(len(tokens))
        gap = max(gap, float(gaps(ref, tokens).max()))
        err = max(err, float(np.abs(
            reported - log_softmax(ref)[idx, tokens]).max()))
        count += len(tokens)
    return {"logit_gap": gap, "logprob_error": err, "tokens": count,
            "requests": len(picked)}


def length_mismatches(picked: Sequence) -> int:
    """Sampled requests that did not serve exactly the tokens asked for, or
    whose reported log-probs do not pair one to one with them."""
    return sum(1 for r in picked
               if r.finish_reason != "length"
               or len(r.tokens) != r.planned.max_new_tokens
               or len(r.top_logprobs) != len(r.tokens))
