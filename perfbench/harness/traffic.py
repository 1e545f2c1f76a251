"""The one traffic generator: every mix is a data file read here.

A mix file states the loop (``closed`` with a number of clients, or
``open`` with a Poisson rate per second), the engine slots and page size,
the seconds of untimed traffic before the window (``warm_s``), and the
prompt and output length distributions: lognormal by median, clipped to a
range.  A mix states no spread: the range is taken as the central 95.4% of
the lognormal (its median times or over two sigma), so sigma is
ln(max / min) / 4.  Each seed gets the same multiset of lengths and of
arrival gaps, in its own order, and its own token ids: the amount of work
does not change with the seed, only its arrangement.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_KEYS = ("loop", "slots", "page_size", "warm_s", "prompt_tokens",
         "output_tokens", "block")


@dataclasses.dataclass
class Req:
    """One generated request: its prompt and output budget, and (open
    loop) when it is due, in seconds after the window opens."""

    rid: int
    prompt: np.ndarray
    max_new: int
    due_s: float = 0.0


def validate(mix: dict) -> dict:
    missing = [k for k in _KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if mix["loop"] == "closed":
        if mix.get("clients", 0) < 1:
            raise ValueError("a closed loop needs clients >= 1")
    elif mix["loop"] == "open":
        if not mix.get("rate_per_s", 0) > 0:
            raise ValueError("an open loop needs rate_per_s > 0")
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for k in ("prompt_tokens", "output_tokens"):
        d = mix[k]
        if not 1 <= d["min"] <= d["median"] <= d["max"]:
            raise ValueError(f"bad length distribution {k}: {d}")
    return mix


def sigma(dist: dict) -> float:
    """The lognormal's sigma: its range is the central 95.4% (+-2 sigma)."""
    return math.log(dist["max"] / dist["min"]) / 4


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of the clipped lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + sigma(dist) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (seconds) at the mid-quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Generator:
    """Requests of one mix for one seed, made on demand in order: blocks of
    ``mix["block"]`` requests, each block a fresh permutation of the same
    lengths and gaps."""

    def __init__(self, mix: dict, seed: int, vocab: int, rid_base: int = 0):
        self.mix = validate(mix)
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        n = mix["block"]
        self._plens = quantile_lengths(mix["prompt_tokens"], n)
        self._outs = quantile_lengths(mix["output_tokens"], n)
        self._gaps = (quantile_gaps(mix["rate_per_s"], n)
                      if mix["loop"] == "open" else np.zeros(n))
        self._block: list[tuple[int, int, float]] = []
        self._due = 0.0
        self._rid = rid_base

    def _refill(self) -> None:
        n = len(self._plens)
        p = self.rng.permutation(n)
        o = self.rng.permutation(n)
        g = self.rng.permutation(n)
        self._block = [(int(self._plens[p[i]]), int(self._outs[o[i]]),
                        float(self._gaps[g[i]])) for i in range(n)][::-1]

    def next(self) -> Req:
        if not self._block:
            self._refill()
        plen, out, gap = self._block.pop()
        self._due += gap
        req = Req(self._rid, self.rng.integers(0, self.vocab, plen,
                                               dtype=np.int32), out,
                  self._due)
        self._rid += 1
        return req

    def in_flight(self, n: int) -> list[Req]:
        """The requests ``n`` closed-loop clients hold in their steady
        state, for a window that opens there rather than at a common
        start.  A client is busy with a request for as long as its output
        takes, so the one in flight has an output length drawn in
        proportion to that length (the block's lengths, weighted by
        themselves, at ``n`` mid-quantiles), and has served a uniform share
        of it so far (``n`` mid-quantiles of [0, 1), paired with the
        lengths in a fixed order).  Its prompt is a prompt of the mix
        followed by as many tokens as it has served, and its output budget
        is what is left.  The multisets of prompt lengths and of
        (served, left) pairs are the same for every seed; the seed deals
        them to the clients and draws the token ids."""
        outs = np.sort(self._outs)
        cdf = np.cumsum(outs) / outs.sum()
        u = (np.arange(n) + 0.5) / n
        length = outs[np.minimum(np.searchsorted(cdf, u), len(outs) - 1)]
        share = (np.random.default_rng(0).permutation(n) + 0.5) / n
        served = np.floor(share * length).astype(np.int64)
        plens = quantile_lengths(self.mix["prompt_tokens"], n)
        p = self.rng.permutation(n)
        o = self.rng.permutation(n)
        reqs = []
        for i in range(n):
            plen = int(plens[p[i]]) + int(served[o[i]])
            reqs.append(Req(self._rid, self.rng.integers(
                0, self.vocab, plen, dtype=np.int32),
                int(length[o[i]] - served[o[i]])))
            self._rid += 1
        return reqs

    def lengths(self) -> tuple[np.ndarray, np.ndarray]:
        """The multiset of prompt and output lengths every block draws."""
        return self._plens, self._outs
