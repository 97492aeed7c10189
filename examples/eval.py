"""Standalone offline evaluation — capability twin of the reference ``eval.py``.

Loads a saved checkpoint into a fresh VGG16, sweeps every image under
``<data>/test/<label>/``, and reports top-1 / top-k accuracy — the reference's
flow (``eval.py:40-72``: cv2 load + resize + ImageNet normalize, batch-1
forward, sklearn ``top_k_accuracy_score`` k=1 and k=2).

TPU-first differences: evaluation is batched (the reference forwards one image
at a time, ``eval.py:60-61``), runs under jit, and top-k is computed with a
correctly-named k (the reference prints k=2 results under a variable called
``acc_top5``, ``eval.py:70-72`` — SURVEY.md §2e).

Usage::

    python examples/eval.py [checkpoint_dir] [test_data_dir]

Defaults: ``./runs/weights/last`` and ``./data/test``.
"""

from __future__ import annotations

import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from distributed_training_pytorch_tpu.checkpoint import CheckpointManager
from distributed_training_pytorch_tpu.data import (
    ImageFolderDataSource,
    ShardedLoader,
    eval_transform,
)
from distributed_training_pytorch_tpu.models import VGG16
from distributed_training_pytorch_tpu.ops import top_k_accuracy
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.utils import enable_compile_cache

LABELS = ["cat", "dog", "snake"]
HEIGHT = WIDTH = 224
BATCH = 64


def evaluate(
    checkpoint_dir: str,
    test_path: str,
    labels=None,
    batch=BATCH,
    *,
    model=None,
    height=None,
    width=None,
    mesh=None,
) -> dict:
    labels = labels or LABELS
    height = height or HEIGHT
    width = width or WIDTH
    import optax

    mesh = mesh or mesh_lib.create_mesh()
    model = model or VGG16(num_classes=len(labels))

    def criterion(logits, b):
        mask = b.get("mask")
        return jnp.zeros(()), {
            "top1": top_k_accuracy(logits, b["label"], k=1, weights=mask),
            "top2": top_k_accuracy(logits, b["label"], k=2, weights=mask),
        }

    engine = TrainEngine(make_supervised_loss(model, criterion), optax.sgd(0.0), mesh)
    state = engine.init_state(
        jax.random.key(0), lambda rng: model.init(rng, jnp.zeros((1, height, width, 3)))
    )
    # Restore params from the named checkpoint (``eval.py:47-50`` analog).
    import os

    mgr = CheckpointManager(os.path.dirname(checkpoint_dir.rstrip("/")), async_save=False)
    state, _ = mgr.restore(checkpoint_dir, state, params_only=True)
    mgr.close()

    source = ImageFolderDataSource(test_path, labels, transform=eval_transform(height, width))
    loader = ShardedLoader(
        source, batch, shuffle=False, drop_last=False, pad_final=True, num_workers=8
    )
    sums: dict[str, float] = {}
    total = 0.0
    for b, host_batch in enumerate(loader):
        # Global real-row count: host-independent aggregation weight.
        weight = float(loader.global_real_count(b))
        metrics = engine.eval_step(state, engine.shard_batch(host_batch))
        for k, v in metrics.items():
            sums[k] = sums.get(k, 0.0) + float(v) * weight
        total += weight
    return {k: v / max(total, 1.0) for k, v in sums.items()}


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    import os

    checkpoint_dir = sys.argv[1] if len(sys.argv) > 1 else "./runs/weights/last"
    test_path = sys.argv[2] if len(sys.argv) > 2 else "./data/test"
    # EVAL_MODEL picks any zoo member (vgg16|resnet50|vit_b16|convnext_l...);
    # default stays the reference's VGG16. EVAL_LABELS is a comma list.
    labels = [s.strip() for s in os.environ.get("EVAL_LABELS", "").split(",") if s.strip()] or None
    model = None
    if os.environ.get("EVAL_MODEL"):
        from distributed_training_pytorch_tpu.models import create_model

        model = create_model(
            os.environ["EVAL_MODEL"], num_classes=len(labels or LABELS)
        )
        # Whether params nest under InputNormalizer's 'inner' scope (the
        # SHIP_UINT8 trainer default) is read from the CHECKPOINT's own meta
        # (manager.save records params_top_level — ADVICE r4: the restore
        # target must match what was trained, not a mutable env var).
        # Checkpoints predating the meta key fall back to the SHIP_UINT8
        # knob + the trainer's model allowlist.
        wrapped = None
        mgr = CheckpointManager(
            os.path.dirname(checkpoint_dir.rstrip("/")), async_save=False
        )
        try:
            # KeyError: checkpoints without a 'meta' item (orbax raises it,
            # not FileNotFoundError) fall back to the env heuristic too.
            top = mgr.read_meta(checkpoint_dir).get("params_top_level")
            if top is not None:
                wrapped = top == ["inner"]
        except (FileNotFoundError, ValueError, KeyError):
            pass
        finally:
            mgr.close()
        if wrapped is None:
            imagenet_family = os.environ["EVAL_MODEL"] in (
                "resnet50", "vit_b16", "convnext_l", "convnext_tiny",
                "resnet18_slim", "vit_tiny",
            )
            wrapped = imagenet_family and os.environ.get("SHIP_UINT8", "1") != "0"
        if wrapped:
            from distributed_training_pytorch_tpu.data import transforms as _T
            from distributed_training_pytorch_tpu.models.wrappers import InputNormalizer

            model = InputNormalizer(
                inner=model, mean=list(_T.IMAGENET_MEAN), std=list(_T.IMAGENET_STD)
            )
    # EVAL_SIZE overrides the 224x224 default (e.g. 32 for the records-path
    # digits proof's ResNet18Slim checkpoints).
    size = int(os.environ.get("EVAL_SIZE", "0")) or None
    results = evaluate(
        checkpoint_dir, test_path, labels=labels, model=model, height=size, width=size
    )
    print(f"ACCURACY TOP-1: {results['top1']:.4f}")
    print(f"ACCURACY TOP-2: {results['top2']:.4f}")
