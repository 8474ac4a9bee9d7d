"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them, with the run's seed, into
requests.

A mix is a closed loop of ``clients`` callers: each sends its next request
as soon as its last one finished.

Every seed gets the same multiset of sizes: lengths are the distribution's
quantiles at fixed, evenly spaced probabilities, and the seed only shuffles
their order and draws the token ids.  So two seeds differ in which request
comes when, not in how much work a run holds.

Mix keys:

- ``clients``: callers in the loop.
- ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
- ``specs``: how many quantiles each length list holds (the pool the
  requests draw from in turn).
- ``warm_decode_steps``: the decode steps that set-up runs before the
  window (at least the smallest output).  The first wave, one request per
  client, has ``clients`` evenly spaced prompt lengths and output budgets
  between ``warm_decode_steps`` and the largest output, in a seeded order:
  completions spread evenly from the window's start, and set-up admits the
  same sizes, so runs the same programs, whatever the seed.
- ``warm_admissions``: how many requests set-up expects to be admitted in
  one prefill at most; it warms the splice of that many (``run.py``).
"""
from __future__ import annotations

import json
import math
import pathlib
import statistics

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the probabilities (k + 1/2) / n of ``dist``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for k in range(n):
        u = (k + 0.5) / n
        if dist["dist"] == "uniform":
            x = lo + u * (hi - lo)
        elif dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            x = dist["median"] * math.exp(dist["sigma"] * z)
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(min(hi, max(lo, int(round(x)))))
    return out


class Traffic:
    """The seeded request stream of one mix: sizes in a seed-shuffled order,
    token ids drawn from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        n = int(mix["specs"])
        self.prompts = self._shuffled(quantile_lengths(mix["prompt_tokens"], n))
        self.outputs = self._shuffled(quantile_lengths(mix["output_tokens"], n))
        self._next = 0

    def _shuffled(self, xs):
        return [xs[i] for i in self.rng.permutation(len(xs))]

    @property
    def max_prompt(self) -> int:
        return int(self.mix["prompt_tokens"]["max"])

    def tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n).astype(np.int32)

    def next_sizes(self) -> tuple[int, int]:
        """The next (prompt length, output budget) of the pool, in turn."""
        i = self._next % len(self.prompts)
        self._next += 1
        return self.prompts[i], self.outputs[i]

    def first_wave(self) -> list[tuple[int, int]]:
        """(prompt length, output budget) of each client's first request:
        evenly spaced quantiles of the prompt lengths, and budgets evenly
        spaced between ``warm_decode_steps`` and the largest output, so
        that at the window's start the budgets left are spread evenly from
        nothing to the rest of the range."""
        c, w = int(self.mix["clients"]), int(self.mix["warm_decode_steps"])
        out = self.mix["output_tokens"]
        if w < int(out["min"]):
            raise ValueError("warm_decode_steps below the smallest output")
        prompts = self._shuffled(quantile_lengths(self.mix["prompt_tokens"], c))
        budgets = self._shuffled(quantile_lengths(
            {"dist": "uniform", "min": w, "max": out["max"]}, c))
        return list(zip(prompts, budgets))
