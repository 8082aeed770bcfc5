"""The one traffic generator. A mix is a JSON file under ``mixes/``; this
module turns it and a seed into the requests of one run.

Every seed gets the same work: lengths are the stratified quantiles
``(k + 0.5) / n`` of the mix's distributions over a block of ``n``
requests; the seed permutes them within the block and draws the token
ids.

The one arrival kind is ``backlog``: a queue that never runs dry, the
harness keeping at least ``waiting`` requests waiting, in blocks of
``block`` requests.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, List

import numpy as np

MIXES = Path(__file__).resolve().parent / "mixes"


@dataclass(frozen=True)
class Planned:
    rid: int
    prompt: List[int]
    max_new: int


def load_mix(name: str) -> dict:
    return json.loads((MIXES / f"{name}.json").read_text())


def seed_words(seed: int, *more: int) -> list:
    """A seed of any size or sign as entropy words for ``default_rng``."""
    return [seed & (2 ** 64 - 1), (seed >> 64) & (2 ** 64 - 1), *more]


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution, rounded and clipped."""
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = dist["lo"] * (dist["hi"] / dist["lo"]) ** u
    return np.clip(np.rint(x), dist["lo"], dist["hi"]).astype(int)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def plan(mix: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """The requests of one run, in submission order."""
    if mix["arrivals"] != "backlog":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    n = mix["block"]
    prompts = quantile(mix["prompt"], _strata(n))
    outputs = quantile(mix["output"], _strata(n))
    for b in range(10 ** 9):
        rng = np.random.default_rng(seed_words(seed, b))
        p_len = rng.permutation(prompts)
        o_len = rng.permutation(outputs)
        for i in range(n):
            toks = np.random.default_rng(seed_words(seed, b, i + 1)).integers(
                0, vocab, int(p_len[i]))
            yield Planned(rid=b * n + i, prompt=toks.tolist(),
                          max_new=int(o_len[i]))
