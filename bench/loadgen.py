"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) states:

- ``loop``: ``open`` (arrivals on a schedule, whether or not earlier
  queries finished) or ``closed`` (``engine.max_concurrency`` queries kept
  outstanding, the next sent as one completes);
- ``source``: where the mix's lengths and load come from;
- ``lengths``: ``{"dist": "fixed", "value": n}``,
  ``{"dist": "lognormal", "median": m, "sigma": s}`` or
  ``{"dist": "normal", "mean": m, "sd": s}``, rounded and clipped to
  ``[lo, hi]``;
- ``tokens``: ``{"dist": "uniform"}`` (ids uniform over ``[1, vocab)``) or
  ``{"dist": "zipf", "a": a}`` (Zipf(a) ranks clipped to the vocabulary);
- open loop: ``rate_qps``, and optionally ``bursts``
  ``{"every_s", "len_s", "mult"}`` (the rate times ``mult`` for ``len_s``
  seconds out of every ``every_s``);
- optionally ``repeats`` ``{"unique": n, "alpha": a}``: payloads drawn
  with Zipf(a) over ranks from a pool of ``n`` distinct queries;
- ``warmup_s``: seconds of the same traffic before the window;
- ``shape_seed``: fixes the multiset of lengths and of inter-arrival gaps.

``--seed`` then orders those lengths and gaps and draws every token id, so
every seed offers the same amount of work in another order, and the same
seed the same inputs.  Zipf token ids are drawn as
``repro.data.workload._zipf_tokens`` draws them.
"""
from __future__ import annotations

import json
from typing import List

import numpy as np

POOL = 1 << 14          # lengths and gaps in the fixed multisets
CHUNK = 4096            # requests drawn per chunk of a stream

LOOPS = ("open", "closed")
TOKENS = ("uniform", "zipf")


def load_mix(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    if mix["loop"] == "open" and not mix.get("rate_qps", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_qps > 0")
    lo, hi = mix["lengths"]["lo"], mix["lengths"]["hi"]
    if not 1 <= lo <= hi:
        raise ValueError(f"{path}: lengths need 1 <= lo <= hi")
    if mix.get("tokens", {}).get("dist") not in TOKENS:
        raise ValueError(f"{path}: tokens.dist must be one of {TOKENS}")
    return mix


def length_pool(mix: dict) -> np.ndarray:
    """The mix's fixed multiset of query lengths (independent of --seed)."""
    spec = mix["lengths"]
    rng = np.random.default_rng([mix["shape_seed"], 1])
    if spec["dist"] == "fixed":
        x = np.full(POOL, float(spec["value"]))
    elif spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], POOL)
    elif spec["dist"] == "normal":
        x = rng.normal(spec["mean"], spec["sd"], POOL)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)


def _seed(seed: int, *tags: int) -> List[int]:
    # numpy seeds take any non-negative integer; tags separate the streams
    return [int(seed) & (2 ** 64 - 1), *tags]


class Stream:
    """Request ``i`` of a run: its length and token ids, drawn in chunks."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.pool = length_pool(mix)
        self._chunks: dict = {}
        rep = mix.get("repeats")
        self._unique = None
        if rep:
            rng = np.random.default_rng(_seed(seed, 3))
            n = int(rep["unique"])
            lens = rng.permutation(np.resize(self.pool, n))
            self._unique = [self._tokens(rng, int(k)) for k in lens]
            p = np.arange(1, n + 1, dtype=np.float64) ** -float(rep["alpha"])
            self._p = p / p.sum()

    def _tokens(self, rng, n: int) -> np.ndarray:
        spec = self.mix["tokens"]
        if spec["dist"] == "uniform":
            return rng.integers(1, self.vocab, size=n, dtype=np.int32)
        ranks = rng.zipf(spec["a"], size=n)
        return np.minimum(ranks, self.vocab - 1).astype(np.int32)

    def chunk(self, c: int) -> List[np.ndarray]:
        if c not in self._chunks:
            rng = np.random.default_rng(_seed(self.seed, 2, c))
            if self._unique is not None:
                idx = rng.choice(len(self._unique), size=CHUNK, p=self._p)
                self._chunks[c] = [self._unique[i] for i in idx]
            else:
                # lengths cycle through permutations of the pool, so every
                # seed sends the same lengths in another order
                first = c * CHUNK
                lens = np.concatenate([
                    np.random.default_rng(_seed(self.seed, 1, k))
                    .permutation(self.pool)
                    for k in range(first // POOL,
                                   (first + CHUNK - 1) // POOL + 1)])
                off = first % POOL
                lens = lens[off:off + CHUNK]
                flat = self._tokens(rng, int(lens.sum()))
                self._chunks[c] = np.split(flat, np.cumsum(lens)[:-1])
        return self._chunks[c]

    def __getitem__(self, i: int) -> np.ndarray:
        return self.chunk(i // CHUNK)[i % CHUNK]

    def lengths_used(self) -> np.ndarray:
        """Every length this stream can send (for the shapes to warm)."""
        if self._unique is not None:
            return np.unique([len(t) for t in self._unique])
        return np.unique(self.pool)


def arrivals(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Due times in [0, horizon_s) of an open loop, in seconds.

    Unit-rate gaps come from a fixed multiset (``shape_seed``) put in an
    order drawn from ``seed``; they are mapped through the cumulative rate,
    so bursts raise the rate without changing the gaps' shape."""
    rate = float(mix["rate_qps"])
    bursts = mix.get("bursts")
    pool = np.random.default_rng([mix["shape_seed"], 4]).exponential(
        1.0, POOL)
    # unit-rate time needed for the horizon, with every burst at full rate
    mult = float(bursts["mult"]) if bursts else 1.0
    need = rate * mult * horizon_s
    reps = int(need // pool.sum()) + 1
    gaps = np.concatenate([
        np.random.default_rng(_seed(seed, 5, k)).permutation(pool)
        for k in range(reps + 1)])
    unit = np.cumsum(gaps)
    unit = unit[unit < need]
    return _invert_rate(unit, rate, bursts, horizon_s)


def _invert_rate(unit: np.ndarray, rate: float, bursts, horizon_s: float
                 ) -> np.ndarray:
    """Map unit-rate event times through the inverse of the cumulative
    rate Lambda(t) = integral of the mix's rate up to t."""
    if not bursts:
        t = unit / rate
        return t[t < horizon_s]
    every, length, mult = (float(bursts["every_s"]), float(bursts["len_s"]),
                           float(bursts["mult"]))
    if not 0 < length <= every:
        raise ValueError("bursts need 0 < len_s <= every_s")
    # Lambda over one period: a burst of `length` at rate*mult first
    per_burst = rate * mult * length
    per_period = per_burst + rate * (every - length)
    k, r = np.divmod(unit, per_period)
    t = np.where(r < per_burst, r / (rate * mult),
                 length + (r - per_burst) / rate)
    t = k * every + t
    return t[t < horizon_s]

