"""The one general traffic generator.  A traffic mix is a data file of
parameters (``perfbench/traffic/<mix>.json``); this module turns it and
a seed into inputs.  Nothing here knows a cell's or a mix's name.

Lengths are *stratified*: ``n`` requests take the ``n`` evenly spaced
quantiles of the stated distribution, so every seed offers the same
multiset of sizes; the seed permutes their order and draws the token
ids.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose, so that adding a draw to one
    never shifts another."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32] + tag)


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution at ``u`` in (0, 1), as whole
    numbers in ``[lo, hi]``."""
    if dist["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = dist["lo"], dist["hi"]
    return np.clip(np.rint(lo + u * (hi - lo)), lo, hi).astype(int)


def stratified_lengths(dist: dict, n: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n, in an order drawn
    from ``rng``."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(quantile(dist, u))


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list:
    return [int(t) for t in rng.integers(1, vocab, n)]


def train_batches(mix: dict, seed: int, vocab: int):
    """An endless stream of host batches ``(ids, ids)``: ``global_batch``
    rows of ``seq_len`` seeded random token ids, no padding, every row
    different."""
    rng = rng_for(seed, "train")
    shape = (mix["global_batch"], mix["seq_len"])
    while True:
        ids = rng.integers(0, vocab, shape, dtype=np.int32)
        yield ids, ids


def first_train_batches(mix: dict, seed: int, vocab: int, n: int) -> list:
    """The first ``n`` batches of :func:`train_batches` (the reference
    redraws them from the seed)."""
    gen = train_batches(mix, seed, vocab)
    return [next(gen)[0] for _ in range(n)]


def serve_requests(mix: dict, seed: int, vocab: int, n: int,
                   cycle: int = 0) -> list:
    """``n`` requests ``{"rid", "prompt", "max_new"}`` with stratified
    prompt and output lengths, ``prompt + max_new <= max_total``.
    ``cycle`` numbers successive draws of a closed loop."""
    shape_rng = rng_for(seed, f"serve{cycle}")
    prompts = stratified_lengths(mix["prompt"], n, shape_rng)
    outputs = stratified_lengths(mix["output"], n, shape_rng)
    rng = rng_for(seed, f"tokens{cycle}")
    cap = mix.get("max_total", 1024)
    reqs = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        p = int(min(p, cap - 1))
        o = int(max(1, min(o, cap - p)))
        reqs.append({"rid": f"c{cycle}r{i}",
                     "prompt": token_ids(rng, max(1, p), vocab),
                     "max_new": o})
    return reqs
