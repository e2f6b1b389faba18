"""The one traffic generator.  A mix is a JSON file under ``traffic/``;
this module reads it and turns ``--seed`` into documents, operations,
exit stages and (open loop) arrival times.

Every seed gets the same work in another order.  Documents come in
blocks of ``block``: each block holds the same stratified set of lengths
(quantiles of the mix's truncated lognormal), the same count of documents
per exit stage (the mix's ``exit_shares``), the same tenants and, in an
open loop, the same stratified set of inter-arrival gaps (exponential
quantiles at the mix's rate).  The seed permutes each block and draws
the words.  So two seeds differ in order and content, not in how much
there is to do, and the spread between runs is the system's.

Keys of a mix file:

  loop         "closed" (a column job: ``in_flight`` documents
               outstanding, a new one submitted as one resolves, over
               whole blocks, as many as ``window_docs_per_s`` times the
               window's seconds rounds to) or "open" (Poisson arrivals at
               ``rate_per_s`` combined over tenants, after ``ramp_s`` of
               arrivals that are served but not measured)
  tenants      queries registered on the one server, each its own handle
  classes      class count of the operation
  length       {"median": words, "sigma": log-sd, "min": tokens,
               "max": tokens}: lognormal, redrawn (truncated) to [min, max]
  operations   {op id: tokens}; ``oracle_op`` is the oracle's operation
  stages       [[model, op, fraction], ...] before the oracle fall-through
  exit_shares  share of documents resolved at each stage, oracle last
  block        documents per stratified block
  trace_s      open loop: a traced run traces the window's last this
               many seconds (a closed loop traces its whole window)
  sample       documents per exit stage in the output check
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Mapping, Sequence, Tuple

import numpy as np

# integer tags that keep the seed's streams apart
_TAG_BLOCK, _TAG_WORDS, _TAG_OPS, _TAG_WARM, _TAG_SAMPLE = 1, 2, 3, 4, 5


def load_mix(path: str) -> Dict[str, Any]:
    with open(path) as f:
        mix = json.load(f)
    if len(mix["exit_shares"]) != len(mix["stages"]) + 1:
        raise ValueError(f"{path}: exit_shares needs one share per stage "
                         "and one for the oracle")
    if abs(sum(mix["exit_shares"]) - 1.0) > 1e-9:
        raise ValueError(f"{path}: exit_shares do not sum to 1")
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"{path}: loop is {mix['loop']!r}")
    return mix


def _seq(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *tags])


def stratified_lengths(length: Mapping[str, float], n: int) -> List[int]:
    """``n`` quantiles, at (i + 1/2) / n, of the lognormal with the given
    median and log-sd, truncated to [min, max] tokens."""
    nd = NormalDist()
    mu, sigma = math.log(length["median"]), length["sigma"]
    lo = nd.cdf((math.log(length["min"]) - mu) / sigma)
    hi = nd.cdf((math.log(length["max"]) - mu) / sigma)
    out = []
    for i in range(n):
        u = lo + (i + 0.5) / n * (hi - lo)
        x = math.exp(mu + sigma * nd.inv_cdf(u))
        out.append(int(min(max(round(x), length["min"]), length["max"])))
    return out


def stratified_counts(shares: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = [s * n for s in shares]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def exponential_gaps(rate: float, n: int) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def words(rng: np.random.Generator, n: int) -> str:
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 2**40, n))


@dataclass(frozen=True)
class Doc:
    index: int                     # position in the stream
    tenant: int
    exit_stage: int                # where the routing resolves it
    n_tokens: int
    text: str
    arrival: float = 0.0           # open loop: seconds from stream start


class Traffic:
    """Documents, operations and schedule of one mix under one seed."""

    def __init__(self, mix: Mapping[str, Any], seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.block = int(mix["block"])
        self._lengths = stratified_lengths(mix["length"], self.block)
        counts = stratified_counts(mix["exit_shares"], self.block)
        self._exits = [s for s, c in enumerate(counts) for _ in range(c)]
        self._tenants = [i % int(mix["tenants"]) for i in range(self.block)]
        self._gaps = (exponential_gaps(float(mix["rate_per_s"]), self.block)
                      if mix["loop"] == "open" else None)
        self._blocks: Dict[int, Tuple[np.ndarray, ...]] = {}
        self._clock: Dict[int, float] = {0: 0.0}
        self._docs: Dict[int, Doc] = {}

    def prefetch(self, n: int) -> None:
        """Make documents 0..n-1 now (set-up), so that the window spends no
        client time writing documents."""
        for k in range(n):
            self.doc(k)

    def window_docs(self, seconds: float) -> int:
        """Documents the window serves: closed loop, whole blocks sized to
        ``seconds`` at the mix's ``window_docs_per_s`` (at least one);
        open loop, those scheduled to arrive before the window closes."""
        if self._gaps is None:
            n = round(seconds * float(self.mix["window_docs_per_s"])
                      / self.block)
            return max(int(n), 1) * self.block
        return self.until(float(self.mix["ramp_s"]) + seconds)

    def until(self, seconds: float) -> int:
        """Open loop: how many documents arrive within ``seconds``."""
        k = 0
        while self.arrival(k) < seconds:
            k += 1
        return k

    # -------------------------------------------------------- operations
    def operations(self) -> Dict[str, str]:
        ops = {}
        for i, (op, n) in enumerate(sorted(self.mix["operations"].items())):
            ops[op] = words(_seq(self.seed, _TAG_OPS, i), int(n))
        return ops

    # --------------------------------------------------------- documents
    def _block(self, b: int):
        got = self._blocks.get(b)
        if got is None:
            rng = _seq(self.seed, _TAG_BLOCK, b)
            got = tuple(rng.permutation(self.block) for _ in range(4))
            self._blocks[b] = got
        return got

    def doc(self, k: int) -> Doc:
        got = self._docs.get(k)
        if got is not None:
            return got
        b, i = divmod(k, self.block)
        p_len, p_exit, p_ten, p_gap = self._block(b)
        n = self._lengths[p_len[i]]
        got = Doc(index=k, tenant=self._tenants[p_ten[i]],
                  exit_stage=self._exits[p_exit[i]], n_tokens=n,
                  text=words(_seq(self.seed, _TAG_WORDS, k), n),
                  arrival=self.arrival(k))
        self._docs[k] = got
        return got

    def arrival(self, k: int) -> float:
        """Open loop: scheduled offset of document ``k`` (0 when closed)."""
        if self._gaps is None:
            return 0.0
        b, i = divmod(k, self.block)
        if b not in self._clock:
            self.arrival(b * self.block - 1)
            self._clock[b] = self._clock[b - 1] + sum(self._gaps)
        p_gap = self._block(b)[3]
        return self._clock[b] + sum(self._gaps[p_gap[j]]
                                    for j in range(i + 1))

    def stream(self, start: int = 0) -> Iterator[Doc]:
        k = start
        while True:
            yield self.doc(k)
            k += 1

    # ------------------------------------------------------------ others
    def warm_docs(self, n: int, n_tokens: int) -> List[str]:
        """Warm-up documents of a given length, from a stream the measured
        documents never draw from."""
        return [words(_seq(self.seed, _TAG_WARM, n_tokens, j), n_tokens)
                for j in range(n)]

    def sample(self, resolved: Sequence[Doc]) -> List[Doc]:
        """The output check's sample: per exit stage, its longest document
        and ``sample - 1`` more drawn from the seed."""
        per = int(self.mix["sample"])
        rng = _seq(self.seed, _TAG_SAMPLE)
        out = []
        for s in sorted({d.exit_stage for d in resolved}):
            docs = sorted((d for d in resolved if d.exit_stage == s),
                          key=lambda d: (-d.n_tokens, d.index))
            pick = [docs[0]]
            rest = docs[1:]
            if rest:
                idx = rng.choice(len(rest), min(per - 1, len(rest)),
                                 replace=False)
                pick += [rest[i] for i in sorted(idx)]
            out += pick
        return out

    def buckets(self, bucket_len) -> List[int]:
        """Length buckets the mix's documents fall in."""
        return sorted({bucket_len(n) for n in self._lengths})


def mix_path(root: str, name: str) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")
