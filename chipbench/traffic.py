"""The one traffic generator: turns a mix's data file and a seed into requests.

Every mix is a JSON file under `chipbench/traffic/` (lengths, rates, bursts).
Lengths are drawn so that every seed gets the same work: the `stratum`
quantiles of each distribution form one block, and each block of `stratum`
consecutive requests is a permutation of that block fixed by the block's index
alone.  The gaps between arrivals are likewise one stratified trace for every
seed.  Under bursts, which request lands in a burst sets the latency tail, so
lengths and gaps keep one order; the seed picks the prompt tokens (and the
run's weights), nothing else.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

DRIVERS = ("backlog", "open_loop")


@dataclass(frozen=True)
class Spec:
    """One generated request: its id, prompt tokens, output budget and the
    offset (seconds from the window's start) at which it is due; backlog
    mixes have no due time (0.0)."""
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0


def seed_words(seed: int) -> list:
    """A seed of any size or sign as non-negative 32-bit words for numpy."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles, at (j + 0.5) / n, of a length or gap
    distribution: `lognormal` (median, sigma), `gamma` (cv; mean 1) or
    `fixed` (value)."""
    qs = [(j + 0.5) / n for j in range(n)]
    if spec["dist"] == "fixed":
        return np.full(n, float(spec["value"]))
    if spec["dist"] == "lognormal":
        nd = NormalDist(math.log(spec["median"]), spec["sigma"])
        return np.array([math.exp(nd.inv_cdf(q)) for q in qs])
    if spec["dist"] == "gamma":
        from scipy.special import gammaincinv
        k = 1.0 / spec["cv"] ** 2          # shape; scale 1/k gives mean 1
        return np.array([gammaincinv(k, q) / k for q in qs])
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def lengths(spec: dict, n: int) -> np.ndarray:
    """Stratified lengths, clipped to [min, max] where the mix states them."""
    vals = np.clip(np.rint(quantiles(spec, n)), spec.get("min", 1),
                   spec.get("max", None))
    return vals.astype(np.int64)


class Traffic:
    """Requests of one mix for one seed, generated lazily and in order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["driver"] not in DRIVERS:
            raise ValueError(f"unknown driver {mix['driver']!r}")
        self.seed = seed_words(seed)
        self.vocab = vocab
        self.k = int(mix["stratum"])
        self.prompt_set = lengths(mix["prompt"], self.k)
        self.output_set = lengths(mix["output"], self.k)
        self.gap_set = None
        if mix["driver"] == "open_loop":
            q = quantiles(mix["gap"], self.k)
            # a block's gaps average exactly 1 / rate: the offered load
            self.gap_set = q / q.mean() / mix["rate_rps"]
        self._blocks = {}
        self._t = 0.0
        self._next = 0

    def _block(self, b: int):
        if b not in self._blocks:
            # one order for every seed: the seed must not change the load
            rng = np.random.default_rng([b, 1])
            perm = [rng.permutation(self.k) for _ in range(2)]
            gaps = np.zeros(self.k)
            if self.gap_set is not None:
                trace = np.random.default_rng([b, 4]).permutation(self.k)
                gaps = self.gap_set[trace]
            self._blocks[b] = (self.prompt_set[perm[0]],
                               self.output_set[perm[1]], gaps)
        return self._blocks[b]

    def spec(self, i: int) -> Spec:
        """Request i; must be asked for in order (due times accumulate)."""
        if i != self._next:
            raise ValueError(f"requests are generated in order: want "
                             f"{self._next}, asked for {i}")
        plen, out, gaps = self._block(i // self.k)
        j = i % self.k
        self._t += float(gaps[j])
        rng = np.random.default_rng(self.seed + [i, 2])
        prompt = rng.integers(0, self.vocab, int(plen[j]), dtype=np.int32)
        self._next += 1
        return Spec(uid=i, prompt=prompt, max_new=int(out[j]), due=self._t)

    def take(self, n: int) -> list:
        return [self.spec(self._next) for _ in range(n)]
