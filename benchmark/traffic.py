"""The one general traffic generator: a pure function of (config, mix, seed).

Every seed gets the SAME multiset of work in another order, so that runs
with different seeds measure the same load:

* the action catalogue (rank -> memory, service time, per-action
  concurrency) is a fixed quantile set of the config's distributions, dealt
  to ranks by the config's own `catalog_seed`, never by `--seed`.
  `actions.concurrency` is an int (every action) or `{"values": [...],
  "weights": [...]}`, dealt from a random stream of its own, so a
  configuration that states one concurrency keeps its catalogue bit for bit;
* `--seed` picks the action names (so their home invokers and probe
  steps), the order of the request sequence and the order of the arrival
  gaps;
* the request sequence is built from blocks holding each rank exactly
  round(block * p_rank) times, each block shuffled by the seed;
* open-loop arrival gaps are the exponential quantiles for the rate,
  shuffled by the seed, so every seed offers exactly the same count over
  exactly the same span.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

SEQ_BLOCK = 65536


@dataclass(frozen=True)
class Catalog:
    namespace: str
    names: List[str]          # by popularity rank, hottest first
    memory_mb: List[int]
    service_s: List[float]
    concurrency: List[int]    # activations one container of the action holds


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, stream])


def _exact_counts(weights, n: int) -> np.ndarray:
    """Largest-remainder split of n into parts proportional to weights."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    cnt = np.floor(raw).astype(np.int64)
    short = n - int(cnt.sum())
    if short:
        cnt[np.argsort(-(raw - cnt), kind="stable")[:short]] += 1
    return cnt


def make_catalog(config: dict, seed: int) -> Catalog:
    spec = config["actions"]
    n = int(spec["count"])
    deal = _rng(int(config["catalog_seed"]), 1)
    mem = np.repeat(np.asarray(spec["memory_mb"], np.int64),
                    _exact_counts(spec["memory_weights"], n))
    deal.shuffle(mem)
    svc = spec.get("service_ms")
    if not svc:
        service = np.zeros(n)
    else:
        if svc["dist"] != "lognormal":
            raise ValueError(f"unknown service_ms dist {svc['dist']!r}")
        ppf = statistics.NormalDist().inv_cdf
        z = np.array([ppf((k + 0.5) / n) for k in range(n)])
        service = np.clip(float(svc["median"]) * np.exp(float(svc["sigma"]) * z),
                          float(svc["min"]), float(svc["max"])) / 1e3
        deal.shuffle(service)
    conc = spec["concurrency"]
    if isinstance(conc, dict):
        conc = np.repeat(np.asarray(conc["values"], np.int64),
                         _exact_counts(conc["weights"], n))
        _rng(int(config["catalog_seed"]), 5).shuffle(conc)
    else:
        conc = np.full(n, int(conc), np.int64)
    top = int(config.get("action_concurrency_max", 1))
    if conc.min() < 1 or conc.max() > top:
        raise ValueError(f"actions.concurrency outside [1, {top}], the "
                         "deployment's action_concurrency_max")
    tag = f"{int(seed):x}"
    return Catalog(
        namespace=str(config["namespace"]),
        names=[f"a{tag}x{k}" for k in range(n)],
        memory_mb=[int(m) for m in mem],
        service_s=[float(s) for s in service],
        concurrency=[int(c) for c in conc])


def popularity(mix: dict, n_actions: int) -> np.ndarray:
    pop = mix["popularity"]
    if pop["dist"] == "zipf":
        w = 1.0 / np.arange(1, n_actions + 1) ** float(pop["exponent"])
    elif pop["dist"] == "uniform":
        w = np.ones(n_actions)
    else:
        raise ValueError(f"unknown popularity dist {pop['dist']!r}")
    return w / w.sum()


class RankSequence:
    """The endless request sequence (popularity ranks), block by block."""

    def __init__(self, mix: dict, n_actions: int, seed: int):
        self._block = np.repeat(
            np.arange(n_actions, dtype=np.int64),
            _exact_counts(popularity(mix, n_actions), SEQ_BLOCK))
        self._rng = _rng(seed, 2)
        self._cur = np.empty(0, np.int64)
        self._i = 0

    def take(self, n: int) -> np.ndarray:
        out = []
        while n > 0:
            if self._i >= len(self._cur):
                self._cur = self._rng.permutation(self._block)
                self._i = 0
            part = self._cur[self._i:self._i + n]
            self._i += len(part)
            n -= len(part)
            out.append(part)
        return np.concatenate(out) if out else np.empty(0, np.int64)

    def next(self) -> int:
        return int(self.take(1)[0])


def arrival_offsets(mix: dict, span_s: float, seed: int,
                    stream: int = 3) -> np.ndarray:
    """Open-loop Poisson arrival times in (0, span_s] at the mix's fixed
    rate: exponential quantile gaps. The count, the set of gaps and the
    last arrival (at span_s) do not depend on the seed."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    n = int(math.floor(rate * span_s))
    if n <= 0:
        return np.empty(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= span_s / gaps.sum()
    _rng(seed, stream).shuffle(gaps)
    return np.cumsum(gaps)
