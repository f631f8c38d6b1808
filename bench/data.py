"""Inputs of a run, made on the host from ``--seed``.

Copies of the repository's synthetic generators (learnable image templates
plus noise; token streams from a fixed sparse Markov chain), kept here so
that the yardstick does not move with the program. Every seed gives the
same sizes; only the values differ.
"""
from __future__ import annotations

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); seeds may exceed 32 bits."""
    return np.random.default_rng([int(seed), int(stream)])


def image_pool(seed: int, n_batches: int, batch: int, image_size: int,
               n_classes: int, noise: float, smooth: int):
    """``n_batches`` distinct (images [B,H,W,3] f32, labels [B] i32)."""
    rng = rng_of(seed, 1)
    raw = rng.normal(0, 1, (n_classes, image_size // smooth,
                            image_size // smooth, 3))
    templates = np.repeat(np.repeat(raw, smooth, 1), smooth, 2)
    pool = []
    for _ in range(n_batches):
        labels = rng.integers(0, n_classes, batch)
        x = templates[labels] + rng.normal(
            0, noise, (batch, image_size, image_size, 3))
        pool.append((x.astype(np.float32), labels.astype(np.int32)))
    return pool


def markov_tokens(rng: np.random.Generator, pref: np.ndarray, vocab: int,
                  batch: int, seq: int, order_bias: float) -> np.ndarray:
    toks = np.empty((batch, seq), np.int32)
    toks[:, 0] = rng.integers(0, vocab, batch)
    for t in range(1, seq):
        follow = rng.random(batch) < (order_bias / (order_bias + 1))
        toks[:, t] = np.where(follow, pref[toks[:, t - 1]],
                              rng.integers(0, vocab, batch))
    return toks


def token_pool(seed: int, n_batches: int, batch: int, seq: int, vocab: int,
               order_bias: float):
    """``n_batches`` distinct {"tokens", "labels"} batches of [B, seq]."""
    rng = rng_of(seed, 2)
    pref = rng.integers(0, vocab, vocab)
    pool = []
    for _ in range(n_batches):
        toks = markov_tokens(rng, pref, vocab, batch, seq + 1, order_bias)
        pool.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    return pool
