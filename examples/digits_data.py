"""Materialize the sklearn `digits` corpus as an image-folder tree.

The only *real* image-classification corpus reachable in this offline
environment (network egress is blocked — CIFAR-10 cannot be downloaded).
1,797 genuine 8x8 grayscale handwritten digits (UCI Optical
Recognition of Handwritten Digits) are upscaled to 32x32 RGB PNGs and laid out
exactly like the reference's dataset tree (``dataset/example_dataset.py:24-30``:
``<root>/<split>/<label>/*.png``), so the full reference flow — ImageFolder
scan, native decode, augment, train, checkpoint, offline ``eval.py`` — runs on
real data end to end.

Split: stratified 80/20 train/test with a fixed seed (1,438 / 359).
"""

from __future__ import annotations

import os

import numpy as np

LABELS = [str(d) for d in range(10)]
SIZE = 32


def materialize(root: str, *, seed: int = 0) -> dict:
    """Write ``<root>/{train,test}/<digit>/*.png``; no-op if already present.

    Returns counts ``{"train": n, "test": n}``.
    """
    import cv2
    from sklearn.datasets import load_digits

    marker = os.path.join(root, ".complete")
    if os.path.exists(marker):
        counts = {}
        for split in ("train", "test"):
            counts[split] = sum(
                len(os.listdir(os.path.join(root, split, lb))) for lb in LABELS
            )
        return counts

    data = load_digits()
    images = data.images  # [1797, 8, 8] float in [0, 16]
    targets = data.target.astype(np.int64)

    rng = np.random.RandomState(seed)
    counts = {"train": 0, "test": 0}
    for digit in range(10):
        idx = np.flatnonzero(targets == digit)
        rng.shuffle(idx)
        n_test = max(1, int(round(0.2 * len(idx))))
        splits = {"test": idx[:n_test], "train": idx[n_test:]}
        for split, members in splits.items():
            d = os.path.join(root, split, str(digit))
            os.makedirs(d, exist_ok=True)
            for i in members:
                img = np.clip(images[i] * (255.0 / 16.0), 0, 255).astype(np.uint8)
                img = cv2.resize(img, (SIZE, SIZE), interpolation=cv2.INTER_NEAREST)
                cv2.imwrite(
                    os.path.join(d, f"{i:04d}.png"),
                    np.repeat(img[:, :, None], 3, axis=2),
                )
            counts[split] += len(members)
    with open(marker, "w") as f:
        f.write("ok\n")
    return counts


if __name__ == "__main__":
    import sys

    root = sys.argv[1] if len(sys.argv) > 1 else "./data/digits"
    print(materialize(root))
