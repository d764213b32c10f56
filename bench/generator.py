"""The one traffic generator: reads a mix from bench/traffic/<mix>.json.

Every seed gets the same work. Lengths and inter-arrival gaps are drawn once
from the mix's own `shape_seed`; the run's `--seed` only permutes them,
within consecutive blocks of `permute_block` requests, and draws the prompt
token ids. So two seeds differ in order and content, never in how much work
the window holds: any window of a few blocks holds the same lengths.

Mix keys read here:
  arrivals    {"kind": "poisson", "rate_per_s": r}  open loop at rate r, or
              {"kind": "backlog", "depth": d}       a queue kept d deep
  prompt_len, output_len
              {"median": m, "sigma": s, "min": lo, "max": hi}: lognormal,
              clipped to [lo, hi]
  table       number of (prompt, output) length pairs drawn (backlog mixes
              cycle through it)
  permute_block
              requests a seed permutes among (default BLOCK): the smaller,
              the less a seed moves which requests share a fill
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass
class Work:
    """Requests in the order they are offered. `arrival_s` is the scheduled
    offset from the start of traffic (all 0 for a backlog)."""
    prompt_lens: np.ndarray
    output_lens: np.ndarray
    arrival_s: np.ndarray
    backlog: int            # 0 = open loop; > 0 = queue depth kept
    seed: int
    vocab: int

    def __len__(self):
        return len(self.prompt_lens)

    def prompt(self, i: int) -> np.ndarray:
        """Prompt token ids of request i: a function of (seed, i) alone."""
        rng = np.random.default_rng([int(self.seed) % 2 ** 64, 1, i])
        return rng.integers(0, self.vocab, int(self.prompt_lens[i]),
                            dtype=np.int32)


def _lognormal(rng, spec, n):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


BLOCK = 32


def block(traffic: dict) -> int:
    """The mix's `permute_block`, else BLOCK."""
    return int(traffic.get("permute_block", BLOCK))


def _block_permutation(rng, n, k):
    """A permutation of range(n) that moves each index only within its
    block of k."""
    return np.concatenate([b + rng.permutation(min(k, n - b))
                           for b in range(0, n, k)])


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (`stream`) of one run seed. Any whole
    number is a valid seed, beyond 32 bits too."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def make(traffic: dict, seed: int, horizon_s: float, vocab: int) -> Work:
    """The requests of one run: enough for `horizon_s` seconds of offered
    load (warm period + window + slack)."""
    shape = np.random.default_rng(traffic.get("shape_seed", 0))
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = int(rate * horizon_s * 1.25) + 32
        gaps = shape.exponential(1.0 / rate, n)
        backlog = 0
    elif arr["kind"] == "backlog":
        n = int(traffic["table"])
        gaps = np.zeros(n)
        backlog = int(arr["depth"])
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    plens = _lognormal(shape, traffic["prompt_len"], n)
    olens = _lognormal(shape, traffic["output_len"], n)
    order, k = run_rng(seed, 0), block(traffic)
    pick = _block_permutation(order, n, k)
    return Work(prompt_lens=plens[pick], output_lens=olens[pick],
                arrival_s=np.cumsum(gaps[_block_permutation(order, n, k)]),
                backlog=backlog, seed=seed, vocab=vocab)
