"""The one traffic generator: a traffic mix is a data file of parameters
(``traffic/<name>.json``) and this module turns it and a seed into a
request stream for a closed loop that keeps ``backlog`` requests
waiting behind ``slots`` running ones.

Every seed gets the same SET of sizes; the seed only orders them and
draws the prompt tokens.  Sizes are stratified: one cycle of ``cycle``
requests holds the distribution's quantiles ``(j + 0.5) / cycle`` for
prompt and output length (paired by a fixed co-prime stride, so long
prompts do not always get long outputs), and each cycle is a fresh
seeded permutation of that set.  So two seeds offer the same work in a
different order, and a window that admits about one cycle sees nearly
the whole set.

``Traffic.stationary`` gives the requests that stand in for a loop that
has run for a long time: one per slot, each a request of the stream's
size law caught at a uniform point of its life (its prompt plus the
tokens it has already served are its prompt here, what it has still to
serve its output).  Their sizes are the same for every seed.

Arrival logic after ``repro.serving.loadgen.poisson_trace`` (seeded
requests), with lengths drawn from a stated distribution rather than
uniform ranges.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class LengthDist:
    dist: str
    lo: int
    hi: int

    def quantile(self, u: np.ndarray) -> np.ndarray:
        if self.dist == "loguniform":
            v = np.exp(math.log(self.lo) +
                       u * (math.log(self.hi) - math.log(self.lo)))
        elif self.dist == "uniform":
            v = self.lo + u * (self.hi - self.lo)
        else:
            raise ValueError(f"unknown length distribution {self.dist!r}")
        return np.clip(np.round(v), self.lo, self.hi).astype(np.int64)


@dataclass(frozen=True)
class TrafficSpec:
    name: str
    slots: int
    max_len: int
    prompt_len: LengthDist
    output_len: LengthDist
    cycle: int
    backlog: int                      # requests kept waiting
    prefix_cache: bool = True
    spare_pages: Optional[int] = None  # KV pages kept beyond every slot's
                                       # max_len; None: the engine's default
    check: Dict = None                # sample for the output check

    @classmethod
    def from_dict(cls, name: str, d: Dict) -> "TrafficSpec":
        if d.get("loop", "closed") != "closed" or \
                d.get("tokens", "uniform") != "uniform":
            raise ValueError(f"traffic {name}: only a closed loop of "
                             f"uniform random tokens is generated")
        spec = cls(
            name=name, slots=int(d["slots"]), max_len=int(d["max_len"]),
            prompt_len=LengthDist(d["prompt_len"]["dist"],
                                  int(d["prompt_len"]["min"]),
                                  int(d["prompt_len"]["max"])),
            output_len=LengthDist(d["output_len"]["dist"],
                                  int(d["output_len"]["min"]),
                                  int(d["output_len"]["max"])),
            cycle=int(d["cycle"]), backlog=int(d["backlog"]),
            prefix_cache=bool(d.get("prefix_cache", True)),
            spare_pages=d.get("spare_pages"),
            check=dict(d["check"]))
        if spec.longest + 1 > spec.max_len:
            raise ValueError(f"traffic {name}: the longest request does not "
                             f"fit max_len {spec.max_len}")
        if spec.backlog < 1:
            raise ValueError(f"traffic {name}: a closed loop needs a backlog")
        return spec

    @classmethod
    def load(cls, path: Path) -> "TrafficSpec":
        return cls.from_dict(Path(path).stem, json.loads(Path(path).read_text()))

    @property
    def longest(self) -> int:
        """Prompt plus output of the longest request the mix can send."""
        return self.prompt_len.hi + self.output_len.hi


def _coprime_stride(n: int, start: int = 0) -> int:
    a = max(1, start, int(round(0.618 * n)))
    while math.gcd(a, n) != 1:
        a += 1
    return a


class Traffic:
    """Request ``i`` of the stream for one seed: its prompt and output
    length."""

    def __init__(self, spec: TrafficSpec, seed: int, vocab: int):
        self.spec, self.seed, self.vocab = spec, int(seed), int(vocab)
        K = spec.cycle
        u = (np.arange(K) + 0.5) / K
        self._plen = spec.prompt_len.quantile(u)
        self._olen = spec.output_len.quantile(u)[
            (np.arange(K) * _coprime_stride(K)) % K]
        self._orders: Dict[int, np.ndarray] = {}

    def _order(self, c: int) -> np.ndarray:
        if c not in self._orders:
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, 1, c]))
            self._orders[c] = rng.permutation(self.spec.cycle)
        return self._orders[c]

    def sizes(self, i: int) -> Tuple[int, int]:
        K = self.spec.cycle
        j = self._order(i // K)[i % K]
        return int(self._plen[j]), int(self._olen[j])

    def tokens(self, tag: int, i: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, tag, i]))
        return rng.integers(0, self.vocab, size=n,
                            dtype=np.int64).astype(np.int32)

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        plen, olen = self.sizes(i)
        return self.tokens(3, i, plen), olen

    def stationary_sizes(self) -> List[Tuple[int, int]]:
        """(prompt, output) of each slot's stand-in request.  A slot of a
        closed loop that has run long holds a request picked with weight
        proportional to its output length (a longer request holds its
        slot longer), caught at a uniform point of it: slot k takes the
        output length at the weighted quantile (k + 0.5) / slots, has
        served the share ((k * stride) mod slots + 0.5) / slots of it,
        and a prompt at the prompt law's quantile by a second stride."""
        n = self.spec.slots
        out = np.sort(self._olen)
        cdf = np.cumsum(out) / out.sum()
        q = (np.arange(n) + 0.5) / n
        length = out[np.minimum(np.searchsorted(cdf, q), len(out) - 1)]
        stride = _coprime_stride(n)
        stride2 = _coprime_stride(n, stride + 1)
        done_share = ((np.arange(n) * stride) % n + 0.5) / n
        prompt = self.spec.prompt_len.quantile(
            ((np.arange(n) * stride2) % n + 0.5) / n)
        served = np.floor(done_share * length).astype(np.int64)
        return [(int(p + s), int(L - s))
                for p, s, L in zip(prompt, served, length)]

    def stationary(self) -> List[Tuple[np.ndarray, int]]:
        return [(self.tokens(4, k, plen), olen)
                for k, (plen, olen) in enumerate(self.stationary_sizes())]
