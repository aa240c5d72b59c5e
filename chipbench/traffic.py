"""Seeded request traffic for one serving cell, read from a mix file.

A mix file (``chipbench/traffic/<mix>.json``) holds parameters only:

* ``source``: where its lengths come from (read by no code);
* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request as
  soon as the last one finished);
* ``arrivals``/``rate_per_s`` for an open loop (``"poisson"``);
* ``pool``: for a closed loop, how many requests one pass of the pool holds;
* ``prompt_tokens``/``output_tokens``: a length distribution each,
  ``lognormal`` (``median``, ``sigma``) or ``uniform``, clipped to
  ``min``..``max``;
* ``engine``: the slot count and ``max_len`` the traffic is sized for.

Every seed gets the same lengths and inter-arrival gaps, in the same
order: they are the distribution's quantiles at ``(i + 0.5) / n``, put in
one fixed shuffled order. The seed draws the prompts' token ids (and the
run's weights), never the amount, order or timing of the work. A window
holds a few tens of requests, so where each long one falls sets much of
what the window measures: on one TPU v5e, a reordering by the seed within
blocks of 4 moved ``qwen2-0.5b.chat``'s output rate by ~10% and its median
time to the first token by ~25% between seeds, while two runs of one seed
mostly agreed to ~1%.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_STREAM_LENGTHS, _STREAM_GAPS, _STREAM_TOKENS = 1, 2, 3


def _rng(seed: int, stream: int, round_: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, round_])


def length_quantiles(spec: dict, n: int) -> list[int]:
    """The ``n`` stratified lengths of one length distribution, ascending."""
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            z = NormalDist().inv_cdf(u)
            x = round(spec["median"] * math.exp(spec["sigma"] * z))
        elif spec["dist"] == "uniform":
            x = lo + math.floor(u * (hi - lo + 1))
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(min(max(x, lo), hi))
    return out


def exponential_quantiles(rate: float, n: int) -> list[float]:
    """The ``n`` stratified inter-arrival gaps of a Poisson process."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def arrange(values: list, stream: int, round_: int = 0) -> list:
    """``values`` in one fixed shuffled order, the same for every seed."""
    return [values[i] for i in _rng(0, stream, round_).permutation(len(values))]


@dataclass
class Draw:
    """One request as the traffic sends it: prompt token ids and how many
    tokens to generate. ``due_s`` is the offset from the window's start at
    which an open loop sends it (``None`` in a closed loop)."""

    index: int
    prompt: list[int]
    max_new_tokens: int
    due_s: float | None = None


class Traffic:
    """The requests of one run: ``open_schedule`` for an open loop,
    ``next_draw`` for a closed one."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix = mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        if self.loop == "open":
            if mix["arrivals"] != "poisson":
                raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
            self.rate = float(mix["rate_per_s"])
            self.pool_size = max(1, math.ceil(self.rate * self.seconds))
        else:
            self.clients = int(mix["clients"])
            self.pool_size = int(mix["pool"])
        self._issued = 0
        self._round = -1
        self._pairs: list[tuple[int, int]] = []

    def _lengths(self, round_: int) -> list[tuple[int, int]]:
        """One pass of the pool: (prompt, output) lengths, arranged."""
        p = length_quantiles(self.mix["prompt_tokens"], self.pool_size)
        o = length_quantiles(self.mix["output_tokens"], self.pool_size)
        # prompt and output lengths are arranged apart: their pairing is
        # one fixed draw too
        return list(zip(arrange(p, _STREAM_LENGTHS, round_),
                        arrange(o, _STREAM_LENGTHS + 10, round_)))

    def _draw(self, index: int, prompt_len: int, output_len: int,
              due_s: float | None = None) -> Draw:
        rng = _rng(self.seed, _STREAM_TOKENS, index)
        return Draw(index, rng.integers(0, self.vocab, prompt_len).tolist(),
                    output_len, due_s)

    def open_schedule(self) -> list[Draw]:
        """Every request of an open loop, in order of its due time."""
        assert self.loop == "open"
        gaps = arrange(exponential_quantiles(self.rate, self.pool_size),
                       _STREAM_GAPS)
        due = np.cumsum(gaps)
        return [self._draw(i, p, o, float(due[i]))
                for i, (p, o) in enumerate(self._lengths(0))]

    def next_draw(self) -> Draw:
        """The next request of a closed loop; the pool is reshuffled once
        each pass is used up."""
        assert self.loop == "closed"
        k = self._issued % self.pool_size
        if k == 0:
            self._round += 1
            self._pairs = self._lengths(self._round)
        p, o = self._pairs[k]
        self._issued += 1
        return self._draw(self._issued - 1, p, o)
