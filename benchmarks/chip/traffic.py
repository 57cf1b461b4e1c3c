"""The one traffic generator: reads a mix's parameters (traffic/<mix>.json)
and turns them, with a seed, into the requests of one run.

Every seed gets the same multiset of sizes and arrival gaps: sizes are the
stratified quantiles (i + 1/2) / block of the mix's distributions, repeated
block by block, and the seed only permutes them within each block and draws
the token ids. So runs with different seeds do the same amount of work in
another order, and a closed-loop window that serves the first few blocks
serves the same sizes on every seed.

Mix keys (all lengths in tokens):

  loop            "closed": the whole backlog is queued at t=0 (callers that
                  each wait for a reply, as many as there are slots);
                  "open": Poisson arrivals at `rate_per_s`.
  slots, page_size, prefill_chunk, prefix_cache, max_burst
                  the server's settings for this traffic (SchedulerConfig).
  prompt, output  {"dist": "uniform", "lo", "hi"} or
                  {"dist": "lognormal", "median", "sigma", "lo", "hi"}.
  documents       optional [{"tokens": n, "count": c}, ...]: long shared
                  prefixes, prefilled during set-up; each request is a
                  document (in turn over a block) followed by its prompt.
  block           requests per stratified block.
  backlog_blocks  closed loop: blocks queued at t=0.
  rate_per_s      open loop: mean arrivals per second.
  horizon         open loop: arrivals are generated for horizon x seconds.
  check_requests  finished requests the correctness check samples.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    """One request as the generator makes it: `tokens` is the whole prompt
    (shared prefix included), `shared` the length of the shared prefix."""

    rid: int
    tokens: np.ndarray
    max_new_tokens: int
    arrival: float
    shared: int = 0


@dataclasses.dataclass(frozen=True)
class Traffic:
    documents: list  # np.ndarray token ids, prefilled during set-up
    window: list  # Items of the measured window


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; any integer seed works."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2)/n of a length distribution,
    as integers clipped to [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
    elif dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def arrival_gaps(rate: float, n: int) -> np.ndarray:
    """Stratified quantiles of the exponential gap of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(rate)


def n_requests(mix: dict, seconds: float) -> int:
    block = int(mix["block"])
    if mix["loop"] == "closed":
        return block * int(mix["backlog_blocks"])
    want = float(mix["rate_per_s"]) * seconds * float(mix["horizon"])
    return block * max(1, math.ceil(want / block))


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    """The requests of one run of `mix` at `seconds` of window."""
    block = int(mix["block"])
    n = n_requests(mix, seconds)
    rng = rng_for(seed, 1)
    docs = []
    for d in mix.get("documents", []):
        docs += [rng.integers(0, vocab, int(d["tokens"]), dtype=np.int32)
                 for _ in range(int(d["count"]))]
    p_q = quantiles(mix["prompt"], block)
    # which output length goes with which prompt length is fixed, the
    # same for every seed (drawn once from a seed of its own)
    o_q = quantiles(mix["output"], block)[rng_for(0, 9).permutation(block)]
    if mix["loop"] == "open":
        g_q = arrival_gaps(float(mix["rate_per_s"]), block)
    items, t = [], 0.0
    for b in range(n // block):
        order = rng.permutation(block)
        doc_of = (rng.permutation(np.arange(block) % len(docs))
                  if docs else None)
        if mix["loop"] == "open":
            gaps = g_q[rng.permutation(block)]
        for i in range(block):
            rid = b * block + i
            prompt = rng.integers(0, vocab, int(p_q[order[i]]),
                                  dtype=np.int32)
            shared = 0
            if docs:
                doc = docs[doc_of[i]]
                prompt = np.concatenate([doc, prompt])
                shared = len(doc)
            if mix["loop"] == "open":
                t += float(gaps[i])
            items.append(Item(rid, prompt, int(o_q[order[i]]),
                              t if mix["loop"] == "open" else 0.0, shared))
    return Traffic(docs, items)
