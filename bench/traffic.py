"""One generator for every traffic mix: a mix is a data file of
parameters (``bench/traffic/<name>.json``), read here.

Sizes and gaps are drawn by stratified quantiles of the mix's
distributions and put in one fixed order, the same for every seed; the
seed writes only the prompt tokens.  So runs with different seeds do
the same work, and differ by no more than two runs of one seed.

Two kinds of loop:

* ``open``: ``rate`` requests per second for the window, Gamma
  inter-arrival gaps of shape ``gap_shape`` (CV = 1/sqrt(shape)),
  scaled so that exactly ``round(rate * seconds)`` requests fall in
  the window.  Users arrive whether or not earlier requests finished.
  The gaps form one cycle, turned so that the window closes on the
  longest of them: the window holds whole bursts and the time to
  serve them, never a burst cut off by its close.
* ``closed``: ``clients`` callers, each sending its next request as
  soon as its previous one has finished; the sizes come from one
  sequence of ``pool`` entries, taken in turn.

Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``.

Prompt tokens follow a Markov chain over the vocabulary (each symbol
prefers a few successors), drawn from the seed.  The program receives
only these token arrays and the output lengths; every request is
greedy.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List

import numpy as np

__all__ = ["Request", "Mix", "load_mix", "quantiles", "schedule",
           "closed_sizes", "prompt_tokens"]


@dataclasses.dataclass(frozen=True)
class Request:
    due: float              # seconds after the window opens (open loop)
    prompt_len: int
    gen_len: int


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str               # "open" | "closed"
    prompt: dict
    output: dict
    rate: float = 0.0       # open: requests per second
    gap_shape: float = 1.0  # open: Gamma shape of the gaps
    clients: int = 0        # closed: concurrent callers
    pool: int = 512         # closed: entries in the size sequence


def load_mix(path) -> Mix:
    d = json.loads(Path(path).read_text())
    unknown = set(d) - {f.name for f in dataclasses.fields(Mix)} | (
        {"name"} & set(d))
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    return Mix(name=Path(path).stem, **d)


def _mixed_seed(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles ((k + 0.5) / n) of a length
    distribution, rounded and clipped to its ``[min, max]``."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + (dist["max"] - dist["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


#: the seed of every mix's order of sizes and gaps
FIXED_ORDER = 20240601


def _gamma_quantiles(shape: float, n: int) -> np.ndarray:
    from scipy.special import gammaincinv
    return gammaincinv(shape, (np.arange(n) + 0.5) / n)


def schedule(mix: Mix, seconds: float) -> List[Request]:
    """The open loop's requests for a window of ``seconds``."""
    n = max(1, int(round(mix.rate * seconds)))
    rng = _mixed_seed(FIXED_ORDER, 1)
    gaps = rng.permutation(_gamma_quantiles(mix.gap_shape, n))
    gaps = np.roll(gaps, -(int(np.argmax(gaps)) + 1))
    due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    plen = rng.permutation(quantiles(mix.prompt, n))
    glen = rng.permutation(quantiles(mix.output, n))
    return [Request(float(t), int(p), int(g))
            for t, p, g in zip(due, plen, glen)]


def closed_sizes(mix: Mix) -> List[Request]:
    """The closed loop's size sequence; callers take entries in turn."""
    rng = _mixed_seed(FIXED_ORDER, 2)
    plen = rng.permutation(quantiles(mix.prompt, mix.pool))
    glen = rng.permutation(quantiles(mix.output, mix.pool))
    return [Request(0.0, int(p), int(g)) for p, g in zip(plen, glen)]


def prompt_tokens(vocab: int, seed: int, index: int, length: int,
                  branching: int = 4) -> np.ndarray:
    """Prompt ``index`` of a run: ``length`` int32 token ids in
    ``[0, vocab)`` from a Markov chain whose successor table comes from
    the seed."""
    table = _mixed_seed(seed, 3).integers(
        0, vocab, size=(min(vocab, 4096), branching))
    rng = _mixed_seed(seed, 1000 + index)
    out = np.empty(length, np.int64)
    cur = int(rng.integers(0, vocab))
    choice = rng.integers(0, branching, size=length)
    for t in range(length):
        out[t] = cur
        cur = int(table[cur % table.shape[0], choice[t]])
    return out.astype(np.int32)
