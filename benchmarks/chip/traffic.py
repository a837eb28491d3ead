"""One generator for every traffic mix: a mix is a JSON file of parameters.

Every seed gets the same work: the lengths of a call's requests are a fixed
multiset, the stratified quantiles of the mix's declared distribution, so
two seeds differ only in the order of the requests and in their token ids.
Keys a mix may set (see ``traffic/*.json``):

  prompt      {"dist": <name>, "min", "max", ...}: the prompt (or, with
              prefixes, the unique question) length; ``dists/<name>.py``
              gives its quantile function from the other keys (lognormal:
              "median", "sigma"; uniform: none)
  prefixes    {"count", "zipf_s", "len": <dist>}: shared prefixes, each
              request prepends one, chosen with Zipf(zipf_s) popularity
  requests_per_call, gen_tokens, warmup_requests
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmarks.chip.timed import load_file

HERE = Path(__file__).resolve().parent


def quantile(dist: dict, u: float, here: Path = HERE) -> int:
    """The ``u``-quantile of a length distribution, clipped to its range."""
    x = load_file(Path(here) / "dists" / f"{dist['dist']}.py",
                  "quantile")(dist, u)
    return int(min(max(round(x), dist["min"]), dist["max"]))


def stratified(dist: dict, n: int, here: Path = HERE) -> list[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n."""
    return [quantile(dist, (i + 0.5) / n, here) for i in range(n)]


def zipf_counts(n_items: int, s: float, n: int) -> list[int]:
    """How many of ``n`` draws land on each of ``n_items`` ranked items
    under Zipf(s), by stratified quantiles of the discrete distribution."""
    w = np.array([1.0 / (k + 1) ** s for k in range(n_items)])
    cdf = np.cumsum(w / w.sum())
    picks = np.searchsorted(cdf, (np.arange(n) + 0.5) / n)
    return [int(c) for c in np.bincount(picks, minlength=n_items)]


class Traffic:
    """The seeded request stream of one mix for one model vocabulary."""

    def __init__(self, mix: dict, vocab: int, seed: int, here: Path = HERE):
        self.mix = mix
        self.vocab = vocab
        self.here = here
        self.rng = np.random.default_rng([seed, 0x7F4A])
        self.prefixes = []
        pre = mix.get("prefixes")
        if pre:
            self.prefixes = [self._tokens(n) for n in
                             stratified(pre["len"], pre["count"], here)]
        self.calls = 0

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def lengths(self, n: int) -> list[tuple[int, int]]:
        """(prefix index or -1, own length) for ``n`` requests: a fixed
        multiset, in an order drawn from the seed."""
        own = stratified(self.mix["prompt"], n, self.here)
        pre = self.mix.get("prefixes")
        if pre:
            idx = [i for i, c in enumerate(zipf_counts(pre["count"],
                                                       pre["zipf_s"], n))
                   for _ in range(c)]
            # a fixed pairing of questions to prefixes, the same for every
            # seed, so every seed gets the same set of prompt lengths
            own = [own[i] for i in np.random.default_rng(0).permutation(n)]
        else:
            idx = [-1] * n
        order = self.rng.permutation(n)
        return [(idx[i], own[i]) for i in order]

    def requests(self, n: int) -> list[np.ndarray]:
        out = []
        lens = self.lengths(n)
        self.last_prefix_lens = [len(self.prefixes[p]) if p >= 0 else 0
                                 for p, _ in lens]
        for p, L in lens:
            toks = self._tokens(L)
            out.append(np.concatenate([self.prefixes[p], toks]) if p >= 0
                       else toks)
        return out

    def warmup(self) -> list[np.ndarray]:
        """Requests that compile every program the window runs and, with
        shared prefixes, put each prefix in the cache once."""
        if self.prefixes:
            q = self.mix["prompt"]["min"]
            return [np.concatenate([p, self._tokens(q)])
                    for p in self.prefixes]
        return self.requests(self.mix.get("warmup_requests", 2))

    def next_call(self) -> list[np.ndarray]:
        self.calls += 1
        return self.requests(self.mix["requests_per_call"])
