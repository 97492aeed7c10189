"""The one traffic generator: training data for a cell, from the seed.

A traffic file gives the shape of the job (sequence length or image size,
global batch, steps an epoch, chain, mesh); the configuration gives what an
item is (``input.kind``: token windows over its vocabulary, or labelled
uint8 images). Every seed gives the same sizes and the same amount of work;
only the values differ.
"""

from __future__ import annotations

import numpy as np


def sub_seeds(seed: int, n: int = 3) -> list[int]:
    """Independent 31-bit seeds for data, weights and the trainer, from any
    whole-number ``--seed`` (the driver's exceed 32 signed bits)."""
    return [int(s) & 0x7FFFFFFF for s in np.random.SeedSequence(int(seed)).generate_state(n)]


def rows_needed(traffic: dict) -> int:
    return traffic["steps_per_epoch"] * traffic["global_batch"]


def make_data(cfg: dict, traffic: dict, data_seed: int) -> dict:
    rng = np.random.default_rng(data_seed)
    kind = cfg["input"]["kind"]
    n = rows_needed(traffic)
    if kind == "token_windows":
        # Log-uniform ids (Zipf, s = 1): a few ids carry most of the mass, as
        # in text, so the embedding's scatter-add sees realistic collisions.
        vocab, t = cfg["vocab_size"], traffic["seq_len"]
        # the entry trains on the first `train_fraction` of the windows it is given
        fraction, rows = cfg["input"].get("train_fraction", 1.0), n
        n = int(np.ceil(rows / fraction))
        while int(n * fraction) < rows:
            n += 1
        u = rng.random((n, t + 1), dtype=np.float32)
        tokens = np.floor(np.exp(u * np.log(vocab))).astype(np.int32) - 1
        return {"windows": np.clip(tokens, 0, vocab - 1)}
    if kind == "images_u8":
        size, classes = traffic["image_size"], cfg["num_classes"]
        n = traffic.get("dataset_size", n)
        labels = rng.integers(0, classes, size=(n,), dtype=np.int32)
        images = rng.integers(0, 160, size=(n, size, size, 3), dtype=np.uint8)
        images += (labels * 8).astype(np.uint8)[:, None, None, None]
        return {"images": images, "labels": labels}
    raise ValueError(f"unknown input kind {kind!r}")
