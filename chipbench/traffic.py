"""The one traffic generator: it reads a mix from ``traffic/<name>.json``
and makes the cell's inputs from ``--seed``.

A ``train`` mix is a closed loop of train steps.  Each step's batch is
``batch x seq_len`` Zipf token ids with a copy-a-recent-token channel,
drawn from ``(seed, step)`` so that every step's rows differ.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterator, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of any size as two 32-bit words (high, low)."""
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return seed >> 32, seed & 0xFFFFFFFF


def _zipf_tokens(rng: np.random.Generator, B: int, T: int, vocab: int,
                 zipf_a: float, structure: float) -> np.ndarray:
    base = rng.zipf(zipf_a, size=(B, T)).astype(np.int64) % vocab
    lags = rng.integers(1, 8, size=(B, 1))
    copy_mask = rng.random((B, T)) < structure
    idx = np.maximum(np.arange(T)[None, :] - lags, 0)
    out = np.where(copy_mask, np.take_along_axis(base, idx, axis=1), base)
    return out.astype(np.int32)


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> Dict[str, np.ndarray]:
    """Batch of train step ``step`` (0-based) for ``seed``."""
    rng = np.random.default_rng((*seed_words(seed), step))
    return {"tokens": _zipf_tokens(rng, mix["batch"], mix["seq_len"], vocab,
                                   mix["zipf_a"], mix["structure"])}


def train_batches(mix: dict, vocab: int, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    step = 0
    while True:
        yield train_batch(mix, vocab, seed, step)
        step += 1
