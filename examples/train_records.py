"""Records-path train-to-accuracy — the PRODUCTION input pipeline, proven.

The reference's whole purpose is train -> checkpoint -> offline-eval accuracy
(``main.py:9-24`` drives the epochs; ``eval.py:69-72`` scores the produced
checkpoint). The two earlier convergence proofs (``train_digits.py`` 99.4%
top-1, ``train_lm.py`` ppl 2.64) run through the ImageFolder and LM-window
sources; this entry proves the *at-scale* path BASELINE configs 3-5 actually
use, end to end on real data:

    real images -> packed ``.rec`` shards (``data.records.pack_image_folder``)
    -> ``NativeRecordTrainSource``: native C++ decode+resize (uint8)
       + native deterministic crop augmentation (uint8)
    -> uint8 over the host->device link (1 byte/px)
    -> ``models.InputNormalizer`` normalizes inside the jitted step
    -> ``Trainer`` (checkpoints, validation, preemption handling)
    -> offline ``examples/eval.py`` of the SAVED checkpoint, through the
       independent ImageFolder eval pipeline — so a label misalignment or
       augmentation bug anywhere in the records path shows up as a top-1 gap.

Corpus: the sklearn digits tree (``digits_data.py`` — the only real image
corpus reachable offline), packed once into 4 train + 2 test shards. Model:
``ResNet18Slim`` (bottleneck ResNet, BN statistics over the global batch) —
a compact member of the ImageNet family whose full-size siblings consume this
exact pipeline. Augmentation is crop-only (``hflip=False``: a mirrored digit
is not a valid digit, same reasoning as ``train_digits.py``).

Env knobs: ``DIGITS_DIR`` (default ./data/digits), ``RECORDS_DIR`` (default
<DIGITS_DIR>/records), ``EPOCHS`` (default 60), ``BATCH`` (global, default
128), ``RECORDS_LR`` (default 0.1, x BATCH/256), ``SAVE_DIR`` (default
./runs/records_digits), ``DTYPE`` (fp32|bf16|fp16 mixed-precision policy —
docs/mixed_precision.md), ``PALLAS`` (1|0 kernel-policy knob: forces the
fused conv1x1+BN+act Pallas path on/off for the ResNet; unset = the
historical auto — ops/dispatch.py, docs/performance.md "Autotuning").
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, ".")

import jax.numpy as jnp
import optax

from distributed_training_pytorch_tpu.data import (
    NativeRecordFileSource,
    NativeRecordTrainSource,
    pack_image_folder,
)
from distributed_training_pytorch_tpu.data import transforms as T
from distributed_training_pytorch_tpu.models import InputNormalizer, ResNet18Slim
from distributed_training_pytorch_tpu.ops import accuracy, cross_entropy_loss, warmup_cosine_lr
from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env
from distributed_training_pytorch_tpu.parallel import mesh_from_env
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache
from examples.digits_data import LABELS, SIZE, materialize
from examples.train_digits import parse_curve


def pack_digits(digits_dir: str, records_dir: str) -> dict:
    """One-time folder-tree -> record-shards conversion (marker-gated)."""
    marker = os.path.join(records_dir, ".complete")
    if not os.path.exists(marker):
        for split, shards in (("train", 4), ("test", 2)):
            pack_image_folder(
                os.path.join(digits_dir, split),
                LABELS,
                os.path.join(records_dir, split),
                num_shards=shards,
            )
        with open(marker, "w") as f:
            f.write("ok\n")
    return {
        split: os.path.join(records_dir, f"{split}-*.rec") for split in ("train", "test")
    }


# DTYPE (mirrors CHAIN_STEPS): fp32|bf16|fp16 — mixed-precision policy +
# model compute dtype together (fp16 auto-enables dynamic loss scaling;
# docs/mixed_precision.md). Unset keeps the historical program: bf16
# model-internal casts under the default (inactive) fp32 policy. Model dtype
# resolves against the trainer's RESOLVED policy (model_dtype_for_entry) so
# an explicit precision= ctor override agrees with build_model.
DTYPE = os.environ.get("DTYPE") or None

# PALLAS (mirrors DTYPE/CHAIN_STEPS/MESH): 1 forces the fused conv1x1+BN+act
# Pallas path in the ResNet's projection shortcuts, 0 forces plain XLA,
# unset = the historical auto. Every resolution is recorded as a
# kernel_dispatch event (ops/dispatch.py).
PALLAS = pallas_from_env()


class RecordsDigitsTrainer(Trainer):
    criterion_uses_mask = True

    def __init__(self, train_pattern: str, val_pattern: str, base_lr: float, **kw):
        self.train_pattern = train_pattern
        self.val_pattern = val_pattern
        self.base_lr = base_lr
        kw.setdefault("precision", DTYPE)  # env default; callers may override
        super().__init__(**kw)

    def build_train_dataset(self):
        return NativeRecordTrainSource(
            self.train_pattern, SIZE, SIZE, pad=4, seed=self.seed, hflip=False
        )

    def build_val_dataset(self):
        # Val ships pre-normalized float32 (native decode+resize+normalize in
        # one C++ call); InputNormalizer's static-dtype dispatch passes float
        # through — mixed uint8-train / f32-val traces one impl each.
        return NativeRecordFileSource(self.val_pattern, height=SIZE, width=SIZE)

    def build_model(self):
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        return InputNormalizer(
            inner=ResNet18Slim(
                num_classes=len(LABELS),
                dtype=model_dtype_for_entry(
                self.precision, DTYPE is not None or self.precision_requested, jnp.bfloat16
            ),
                pallas=PALLAS,
            ),
            mean=list(T.IMAGENET_MEAN),
            std=list(T.IMAGENET_STD),
        )

    def build_criterion(self):
        def criterion(logits, batch):
            mask = batch.get("mask")
            loss = cross_entropy_loss(logits, batch["label"], weights=mask)
            return loss, {
                "ce_loss": loss,
                "accuracy": accuracy(logits, batch["label"], weights=mask),
            }

        return criterion

    def build_scheduler(self):
        steps_per_epoch = max(1, len(self.train_dataset) // self.batch_size)
        lr = self.base_lr * self.batch_size / 256.0  # Goyal et al. scaling
        return warmup_cosine_lr(lr, self.max_epoch, steps_per_epoch, warmup_epochs=5)

    def build_optimizer(self, schedule):
        return optax.chain(
            optax.add_decayed_weights(1e-4), optax.sgd(schedule, momentum=0.9)
        )


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    digits_dir = os.environ.get("DIGITS_DIR", "./data/digits")
    records_dir = os.environ.get("RECORDS_DIR", os.path.join(digits_dir, "records"))
    save_dir = os.environ.get("SAVE_DIR", "./runs/records_digits")
    counts = materialize(digits_dir)
    patterns = pack_digits(digits_dir, records_dir)
    print(f"digits corpus: {counts}; records under {records_dir}")

    Trainer.distributed_setup()
    trainer = RecordsDigitsTrainer(
        train_pattern=patterns["train"],
        val_pattern=patterns["test"],
        base_lr=float(os.environ.get("RECORDS_LR", "0.1")),
        max_epoch=int(os.environ.get("EPOCHS", "60")),
        batch_size=int(os.environ.get("BATCH", "128")),
        chain_steps=int(os.environ.get("CHAIN_STEPS", "1")),
        # MESH (the CHAIN_STEPS/DTYPE convention): a mesh spec like
        # "fsdp4x2" or "dp2fsdp2tp2" trains sharded end to end
        # (docs/parallelism.md); unset = the historical pure-DP program.
        mesh=mesh_from_env(),
        # TELEMETRY=1 (mirrors DTYPE/CHAIN_STEPS): telemetry subsystem —
        # docs/observability.md. Unset = historical program.
        telemetry=os.environ.get("TELEMETRY") == "1" or None,
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=int(os.environ.get("SAVE_PERIOD", "10")),
        # save `last` on the validation cadence (see train_digits.py)
        last_save_period=int(os.environ.get("SAVE_PERIOD", "10")),
        save_folder=save_dir,
        snapshot_path=os.environ.get("SNAPSHOT") or None,
        logger=Logger("records-digits", os.path.join(save_dir, "logfile.log")),
    )
    trainer.train()

    # Offline eval of the SAVED checkpoint through the INDEPENDENT ImageFolder
    # eval pipeline (examples/eval.py) — cross-checks the records packing,
    # native decode, and augmentation against untouched loose files.
    from examples.eval import evaluate

    results = {}
    for name in ("best", "last"):
        ckpt = os.path.join(save_dir, "weights", name)
        if os.path.isdir(ckpt):
            results[name] = evaluate(
                ckpt,
                os.path.join(digits_dir, "test"),
                labels=LABELS,
                model=trainer.model,
                height=SIZE,
                width=SIZE,
            )
            print(
                f"[{name}] ACCURACY TOP-1: {results[name]['top1']:.4f}  "
                f"TOP-2: {results[name]['top2']:.4f}"
            )
    summary = {
        "description": (
            "Third train-to-accuracy proof: the at-scale "
            "records input path — RecordFileSource shards, native C++ "
            "decode/augment, uint8 ship, on-device normalize — trained to "
            "accuracy and offline-evaluated through the independent "
            "ImageFolder eval pipeline."
        ),
        "pipeline": "pack_image_folder -> NativeRecordTrainSource (native decode+crop, uint8) -> InputNormalizer -> Trainer -> checkpoint -> examples/eval.py (ImageFolder path)",
        "model": "ResNet18Slim (bottleneck ResNet, bf16 activations, global-batch BN)",
        "corpus": "sklearn digits (real), packed into 4 train + 2 test .rec shards",
        "train_images": counts["train"],
        "test_images": counts["test"],
        "epochs": trainer.max_epoch,
        "batch": trainer.batch_size,
        "base_lr": trainer.base_lr,
        "results": results,
        "curve": parse_curve(os.path.join(save_dir, "logfile.log")),
    }
    with open(os.path.join(save_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("summary ->", os.path.join(save_dir, "summary.json"))
    Trainer.destroy_process()
