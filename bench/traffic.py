"""The one traffic generator: a mix file in, a list of requests out.

Every run of a cell serves the same work in the same order, so that runs on
different seeds differ by their tokens, not by their load. Lengths (and, in
an open loop, the gaps between arrivals) come in blocks of ``block``
requests: each block holds the same stratified sample of the length
distribution, at the quantiles ``(j + 0.5) / block``, in an order that is
fixed for the mix (``ORDER``), as is the system prompt each request starts
with. The seed picks the token ids of every prompt and system prompt (and,
elsewhere, the weights).

A mix file holds:

  loop        "closed" (``clients`` callers, each waiting for its reply) or
              "open" (Poisson arrivals at ``rate`` requests a second)
  block       requests per stratified block
  prompt      {median, sigma, min, max}: log-normal prompt (or user-turn)
              length, clipped
  output      {median, sigma, min, max}: log-normal output length, clipped
  shared      {count, length}: ``count`` system prompts of ``length``
              tokens; each request starts with one of them, used equally
              often in every block (count 0: no shared prefix)
  max_len     the engine's per-request length limit; every request fits
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    arrival_s: float = 0.0      # offset from the window's start (open loop)
    prefix: int = -1            # which shared system prompt, -1 for none


def seed_words(seed: int) -> List[int]:
    """A non-negative seed of any size as 32-bit words (numpy and JAX both
    take these), least significant first."""
    if seed < 0:
        raise ValueError("seeds are non-negative")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def lognormal_quantiles(dist: dict, k: int) -> np.ndarray:
    """``k`` lengths at the quantiles (j + 0.5) / k of a log-normal with the
    given median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((j + 0.5) / k) for j in range(k)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def exponential_quantiles(rate: float, k: int) -> np.ndarray:
    q = (np.arange(k) + 0.5) / k
    return -np.log1p(-q) / rate


#: seeds the order of lengths, gaps and system prompts within each block:
#: the same for every run
ORDER = 0


class Requests:
    """The cell's requests for one seed, in the order they are issued,
    made a block at a time as the window asks for them."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab = mix, vocab
        self.k = int(mix["block"])
        self.rng = np.random.default_rng(seed_words(seed))
        self.order = np.random.default_rng(ORDER)
        shared = mix.get("shared") or {"count": 0, "length": 0}
        self.n_pre, pre_len = int(shared["count"]), int(shared["length"])
        self.prefixes = [self.rng.integers(0, vocab, pre_len, dtype=np.int32)
                         for _ in range(self.n_pre)]
        self.p_block = lognormal_quantiles(mix["prompt"], self.k)
        self.o_block = lognormal_quantiles(mix["output"], self.k)
        if pre_len + self.p_block.max() + self.o_block.max() > mix["max_len"]:
            raise ValueError("the mix can draw a request longer than max_len")
        self.open = mix["loop"] == "open"
        if self.open:
            self.g_block = exponential_quantiles(float(mix["rate"]), self.k)
        self.reqs: List[Req] = []
        self.t = 0.0

    def __getitem__(self, i: int) -> Req:
        while i >= len(self.reqs):
            self._block()
        return self.reqs[i]

    def _block(self) -> None:
        k, rng, order = self.k, self.rng, self.order
        b = len(self.reqs) // k
        plens = order.permutation(self.p_block)
        olens = order.permutation(self.o_block)
        gaps = order.permutation(self.g_block) if self.open else None
        which = (order.permutation(np.arange(k) % self.n_pre) if self.n_pre
                 else np.full(k, -1))
        for j in range(k):
            body = rng.integers(0, self.vocab, int(plens[j]), dtype=np.int32)
            pre = int(which[j])
            prompt = (np.concatenate([self.prefixes[pre], body]) if pre >= 0
                      else body)
            if gaps is not None:
                self.t += float(gaps[j])
            self.reqs.append(Req(rid=b * k + j, prompt=prompt,
                                 max_new=int(olens[j]), arrival_s=self.t,
                                 prefix=pre))


def generate(mix: dict, vocab: int, seed: int, n: int) -> List[Req]:
    """The first ``n`` requests of the cell for ``seed``."""
    reqs = Requests(mix, vocab, seed)
    return [reqs[i] for i in range(n)]
