"""VGG16 on real data, end to end — the accuracy-clause run.

The reference's whole purpose is train-to-accuracy (``main.py:9-24`` drives the
epochs; ``eval.py:69-72`` measures top-1/top-k of the produced checkpoint).
This entry reproduces that loop on the only real image corpus reachable
offline (sklearn digits — see ``digits_data.py``): materialize the image
folders, train the reference-parity :class:`ExampleTrainer` stack (VGG16,
SGD 0.9-momentum + 1e-4 wd, MultiStepLR), save best/last checkpoints, then
evaluate the *saved checkpoint* with ``examples/eval.py``'s ``evaluate()`` and
print the measured top-1 — the number recorded in docs/digits_accuracy.json.

Digits-specific deviations from the reference recipe (both documented, both
dataset-appropriate, exactly as the reference's own pipeline is tuned to its
3-class photo task):

* the train transform drops the orientation-destroying ops (rotate90, h/v
  flip — a mirrored "2" or rotated "6" is not a valid digit) and keeps the
  photometric ones;
* base lr defaults to 0.02 (env ``DIGITS_LR``): VGG16 has no BatchNorm, and
  the reference's 0.1 assumes its batch-16 photo config.

Env knobs: ``DIGITS_DIR`` (default ./data/digits), ``EPOCHS`` (default 150),
``BATCH`` (global, default 128), ``DIGITS_LR``, ``SAVE_DIR`` (default
./runs/digits), ``DTYPE`` (fp32|bf16|fp16 mixed-precision policy, default
fp32 — docs/mixed_precision.md), ``TELEMETRY`` (1 = event log + goodput +
train-health stats + MFU — docs/observability.md), ``MESH`` (a mesh spec
like ``fsdp4x2`` or ``dp2fsdp2tp2`` — sharded FSDP/TP training,
docs/parallelism.md; unset = pure DP), ``PALLAS`` (1|0 kernel-policy knob,
unset = per-model auto — ops/dispatch.py; a no-op recorded as such for
VGG16).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, ".")

from distributed_training_pytorch_tpu.data import ImageFolderDataSource
from distributed_training_pytorch_tpu.data.transforms import (
    Compose,
    clahe,
    normalize,
    random_brightness_contrast,
    random_gamma,
    resize,
)
from distributed_training_pytorch_tpu.ops import multistep_lr
from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env
from distributed_training_pytorch_tpu.parallel import mesh_from_env
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache
from examples.digits_data import LABELS, SIZE, materialize
from examples.example_trainer import ExampleTrainer


def digits_train_transform(height: int, width: int, *, seed: int = 0, p: float = 0.5):
    """The reference train pipeline minus orientation ops (see module doc)."""
    return Compose(
        [
            resize(height, width),
            clahe(p),
            random_brightness_contrast(p),
            random_gamma(p),
            normalize(),
        ],
        seed=seed,
    )


class DigitsTrainer(ExampleTrainer):
    base_lr = float(os.environ.get("DIGITS_LR", "0.02"))
    # PALLAS (mirrors DTYPE/CHAIN_STEPS/MESH): kernel-policy knob, resolved
    # at the entry and passed down as a constructor-level value — the
    # library never reads env (ops/dispatch.py). Unset = historical program.
    pallas = pallas_from_env()

    def build_train_dataset(self):
        return ImageFolderDataSource(
            self.train_path,
            self.labels,
            transform=digits_train_transform(self.height, self.width, seed=self.seed),
        )

    def build_scheduler(self):
        steps_per_epoch = max(1, len(self.train_dataset) // self.batch_size)
        return multistep_lr(
            self.base_lr, [50, 100, 200], gamma=0.1, steps_per_epoch=steps_per_epoch
        )


def parse_curve(logfile: str) -> list[dict]:
    """Per-epoch (train loss, val accuracy) pairs from the run's logfile —
    the training curve recorded in-repo alongside the final number."""
    import re

    curve: dict[int, dict] = {}
    epoch = None
    with open(logfile) as f:
        for line in f:
            m = re.search(r"Epoch (\d+)/", line)
            if m:
                epoch = int(m.group(1))
            if "TOTAL GLOBAL TRAINING LOSS" in line and epoch is not None:
                lm = re.search(r"ce_loss = ([0-9.eE+-]+)", line)
                if lm:
                    curve.setdefault(epoch, {"epoch": epoch})["train_ce"] = float(
                        lm.group(1)
                    )
            if "VALIDATE RESULTS" in line and epoch is not None:
                am = re.search(r"accuracy = ([0-9.eE+-]+)", line)
                if am:
                    curve.setdefault(epoch, {"epoch": epoch})["val_acc"] = float(
                        am.group(1)
                    )
    return [curve[k] for k in sorted(curve)]


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    data_dir = os.environ.get("DIGITS_DIR", "./data/digits")
    save_dir = os.environ.get("SAVE_DIR", "./runs/digits")
    counts = materialize(data_dir)
    print(f"digits corpus: {counts}")

    Trainer.distributed_setup()
    trainer = DigitsTrainer(
        train_path=os.path.join(data_dir, "train"),
        val_path=os.path.join(data_dir, "test"),
        labels=LABELS,
        height=SIZE,
        width=SIZE,
        max_epoch=int(os.environ.get("EPOCHS", "150")),
        batch_size=int(os.environ.get("BATCH", "128")),
        chain_steps=int(os.environ.get("CHAIN_STEPS", "1")),
        # MESH (the CHAIN_STEPS/DTYPE convention): a mesh spec like
        # "fsdp4x2" or "dp2fsdp2tp2" trains sharded end to end
        # (docs/parallelism.md); unset = the historical pure-DP program.
        mesh=mesh_from_env(),
        # DTYPE (mirrors CHAIN_STEPS): fp32|bf16|fp16 mixed-precision policy;
        # the model's activation dtype follows via ExampleTrainer.build_model
        # (docs/mixed_precision.md). Default fp32 = reference parity.
        precision=os.environ.get("DTYPE") or None,
        # TELEMETRY=1 (mirrors DTYPE/CHAIN_STEPS): events JSONL under
        # SAVE_DIR/telemetry, goodput buckets, on-device train-health stats,
        # per-window MFU (docs/observability.md). Unset = historical program.
        telemetry=os.environ.get("TELEMETRY") == "1" or None,
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=int(os.environ.get("SAVE_PERIOD", "25")),
        # `last` is saved on the validation cadence rather than the
        # reference's every-epoch default (a full-state save per epoch would
        # dominate a run of seconds-long epochs).
        last_save_period=int(os.environ.get("SAVE_PERIOD", "25")),
        save_folder=save_dir,
        snapshot_path=os.environ.get("SNAPSHOT") or None,
        logger=Logger("digits-vgg16", os.path.join(save_dir, "logfile.log")),
    )
    trainer.train()

    # Offline eval of the SAVED checkpoint via the eval twin (ref eval.py flow).
    from examples.eval import evaluate

    results = {}
    for name in ("best", "last"):
        ckpt = os.path.join(save_dir, "weights", name)
        if os.path.isdir(ckpt):
            results[name] = evaluate(
                ckpt,
                os.path.join(data_dir, "test"),
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
        "corpus": "sklearn digits (real, offline stand-in for CIFAR-10)",
        "train_images": counts["train"],
        "test_images": counts["test"],
        "epochs": trainer.max_epoch,
        "batch": trainer.batch_size,
        "base_lr": DigitsTrainer.base_lr,
        "results": results,
        "curve": parse_curve(os.path.join(save_dir, "logfile.log")),
    }
    with open(os.path.join(save_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("summary ->", os.path.join(save_dir, "summary.json"))
    Trainer.destroy_process()
