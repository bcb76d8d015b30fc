"""The comparison that decides a run's ``correct``.

After the window has closed, a sample of the requests the window
finished, drawn from the seed and always holding the one with the most
served tokens, is run through the plain reference (``reference.py``):
each prompt with its served tokens, teacher-forced.  At every served
token's position the gap is how far the reference's logit of that token
lies below the reference's best, over the largest reference logit in
magnitude there.  A greedy server that computes what the reference
computes reads 0 at nearly every position, and a rounding-sized gap
where two logits nearly tie.  ``token_gap`` is the widest gap over the
sample; it is held to the configuration's limit (``check.token_gap`` in
its file).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

__all__ = ["gaps", "sample", "teacher_rows", "judge", "verdict",
           "AT_LEAST"]

#: checks whose limit is a least value; every other is a largest
AT_LEAST = ("requests_checked",)


def gaps(ref_rows: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per position: (best - logit of the served token) / max|logit|."""
    r = np.asarray(ref_rows, np.float64)
    t = np.asarray(tokens, np.int64)
    best = r.max(axis=-1)
    mine = r[np.arange(len(t)), t]
    return (best - mine) / np.maximum(np.abs(r).max(axis=-1), 1e-30)


def sample(done: List[dict], seed: int, *, min_tokens: int,
           min_requests: int, max_requests: int) -> List[dict]:
    """Requests to check: the one with most served tokens, then others
    in a seeded order until ``min_requests`` requests and ``min_tokens``
    served tokens are covered, or ``max_requests`` are taken."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: (-len(done[i]["tokens"]),
                                                    done[i]["id"]))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for i in [first] + rest:
        if len(out) >= max_requests or (len(out) >= min_requests
                                        and n >= min_tokens):
            break
        out.append(done[i])
        n += len(done[i]["tokens"])
    return out


def teacher_rows(prompt: np.ndarray, tokens: Sequence[int]):
    """(sequence, rows): the prompt followed by every served token but
    the last, and the positions whose logits chose the served tokens."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return seq, rows


def judge(rows_fn, picked: List[dict]) -> dict:
    """``token_gap`` (widest gap) and the tokens it covers, with the
    reference's rows from ``rows_fn(sequence, rows)``."""
    worst, n = 0.0, 0
    for req in picked:
        seq, rows = teacher_rows(req["prompt"], req["tokens"])
        g = gaps(rows_fn(seq, rows), req["tokens"])
        worst = max(worst, float(g.max()))
        n += len(g)
    return {"token_gap": worst, "tokens_checked": n,
            "requests_checked": len(picked)}


def verdict(got: dict, failed: int, limit: float, min_requests: int):
    """``(correct, checks)``: a run is correct when no finished request
    failed, at least ``min_requests`` were held to the reference, and
    its ``token_gap`` is within ``limit``.  ``checks`` gives each number
    compared beside its limit, in the order they are printed."""
    checks = {"token_gap": {"value": got["token_gap"], "limit": limit},
              "failed_requests": {"value": failed, "limit": 0},
              "requests_checked": {"value": got["requests_checked"],
                                   "limit": min_requests}}
    correct = (failed == 0 and got["requests_checked"] >= min_requests
               and got["token_gap"] <= limit)
    return bool(correct), checks
