"""ImageNet-scale training entry — BASELINE.json configs 3-5.

One entry for the three scale-out configs (the reference has a single config
in ``main.py:9-22``; these extend its capability surface per BASELINE.json):

=============  ==============================  =========================================
``MODEL=``     BASELINE config                 recipe
``resnet50``   3: ResNet-50 / ImageNet-1k      SGD momentum, 5-epoch warmup + cosine
``vit_b16``    4: ViT-B/16 / ImageNet-1k       AdamW, cosine, patch-embed + MHA
``convnext_l`` 5: ConvNeXt-L / ImageNet-21k    AdamW, bf16 + gradient accumulation
=============  ==============================  =========================================

Data comes from sharded record files (``data.records`` — pack a folder tree
once with ``python -m distributed_training_pytorch_tpu.data.records`` or
``pack_image_folder``); loose-file ImageFolder scans do not scale to 1.2M+
images. When ``IMAGENET_RECORDS`` is unset, a synthetic in-memory set with the
right shapes runs instead, so every config is smoke-runnable anywhere
(``STEPS_PER_EPOCH`` caps an epoch for timed runs).

Launch: ``MODEL=convnext_l ./run.sh`` (single host) or with the coordinator
env for pods (see run.sh). Env knobs: ``IMAGENET_RECORDS`` (glob or dir of
.rec shards), ``VAL_RECORDS``, ``EPOCHS``, ``BATCH`` (global), ``ACCUM``
(grad-accum microsteps; default 4 for convnext_l else 1), ``BASE_LR``,
``IMAGE_SIZE`` (default 224), ``NUM_CLASSES`` (default 1000; 21841 for
convnext_l), ``SAVE_DIR``, ``SNAPSHOT``, ``PROFILE_DIR``, ``DTYPE``
(fp32|bf16|fp16 mixed-precision policy — docs/mixed_precision.md),
``PALLAS`` (1|0 kernel-policy knob: flash attention for ViT, fused
GEMM+epilogues for ResNet/ConvNeXt; unset = per-model auto —
ops/dispatch.py, docs/performance.md "Autotuning").
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, ".")

import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu.data import ArrayDataSource, RecordFileSource
from distributed_training_pytorch_tpu.data import transforms as T
from distributed_training_pytorch_tpu.models import create_model
from distributed_training_pytorch_tpu.ops import accuracy, cross_entropy_loss, warmup_cosine_lr
from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env
from distributed_training_pytorch_tpu.parallel import mesh_from_env
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache
from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng

RECIPES = {
    "resnet50": dict(num_classes=1000, optimizer="sgd", base_lr=0.1, accum=1, wd=1e-4),
    "vit_b16": dict(num_classes=1000, optimizer="adamw", base_lr=1e-3, accum=1, wd=0.05),
    "convnext_l": dict(num_classes=21841, optimizer="adamw", base_lr=1e-3, accum=4, wd=0.05),
    # CPU-smokeable stand-in for the convnext_l recipe (same optimizer/accum
    # path; ConvNeXt-L itself takes too long to compile on a CPU host).
    "convnext_tiny": dict(num_classes=21841, optimizer="adamw", base_lr=1e-3, accum=4, wd=0.05),
}


def _ship_uint8() -> bool:
    """SHIP_UINT8=1 (default): the host pipeline stays uint8 end-to-end and
    normalization runs on device (models.wrappers.InputNormalizer, fused by
    XLA into the first conv) — the host->device link carries 4x fewer bytes
    than pre-normalized float32 and the host skips a float pass (effect not
    measured on today's chip). Same math, same augmentation
    stream; SHIP_UINT8=0 restores host-side normalize.

    NOTE: the wrapper nests the model's params under an ``inner`` scope, so
    the CHECKPOINT TREE depends on this knob — keep it consistent across a
    run's save/resume/eval (snapshots from builds before r4, or from
    SHIP_UINT8=0, restore only with SHIP_UINT8=0)."""
    return os.environ.get("SHIP_UINT8", "1") != "0"


def train_transform(image_size: int, seed: int, ship_uint8: bool = True) -> T.Compose:
    """Random-resized-crop + flip (+ normalize unless shipping uint8),
    Philox-keyed per (epoch, index) — the at-scale analog of the reference's
    albumentations pipeline (``dataset/example_dataset.py:35-46``)."""
    ops = [
        T.random_resized_crop(image_size, image_size),
        T.horizontal_flip(),
    ]
    if not ship_uint8:
        ops.append(T.normalize())
    return T.Compose(ops, seed=seed)


def eval_transform(image_size: int) -> T.Compose:
    return T.eval_transform(image_size, image_size)


def synthetic_source(n: int, image_size: int, num_classes: int, transform, seed: int):
    """Class-separable synthetic images, uint8 — shapes/dtypes of the real
    pipeline without the corpus."""
    rng = np.random.RandomState(seed)
    y = rng.randint(0, num_classes, size=(n,)).astype(np.int32)
    x = (rng.randn(n, image_size, image_size, 3) * 40 + 110 + (y % 13)[:, None, None, None] * 9)
    return ArrayDataSource(transform=transform, image=x.clip(0, 255).astype(np.uint8), label=y)


class _LimitedSource:
    """Length-capping view over a source — ``STEPS_PER_EPOCH`` for timed runs
    without touching the underlying corpus."""

    def __init__(self, source, max_records: int):
        self.source = source
        self.transform = getattr(source, "transform", None)
        self._len = min(len(source), max_records)
        # Forward the loader's whole-batch fast path: hiding a source's
        # load_batch would silently drop native decode+augment (the capped
        # row indices are valid for the underlying source unchanged).
        if hasattr(source, "load_batch"):
            self.load_batch = source.load_batch

    def __len__(self):
        return self._len

    def __getitem__(self, index):
        return self.source[index]


# DTYPE (mirrors CHAIN_STEPS): fp32|bf16|fp16 — mixed-precision policy +
# model compute dtype together (fp16 auto-enables dynamic loss scaling;
# docs/mixed_precision.md). Unset keeps the historical program: bf16
# model-internal casts under the default (inactive) fp32 policy. Model dtype
# resolves against the trainer's RESOLVED policy (model_dtype_for_entry) so
# an explicit precision= ctor override agrees with build_model.
DTYPE = os.environ.get("DTYPE") or None

# PALLAS (mirrors DTYPE/CHAIN_STEPS/MESH): 1 forces the fused Pallas paths
# (ViT flash attention, ResNet conv1x1_bn_act, ConvNeXt dense+gelu), 0
# forces plain XLA, unset = per-model auto (the historical defaults). Every
# resolution is recorded as a kernel_dispatch event (ops/dispatch.py).
PALLAS = pallas_from_env()


class ImageNetTrainer(Trainer):
    criterion_uses_mask = True

    def __init__(self, model_name: str, image_size: int, base_lr: float, **kw):
        self.model_name = model_name
        self.image_size = image_size
        self.base_lr = base_lr
        self.recipe = RECIPES[model_name]
        self.num_classes = int(os.environ.get("NUM_CLASSES", self.recipe["num_classes"]))
        self.train_records = os.environ.get("IMAGENET_RECORDS")
        self.val_records = os.environ.get("VAL_RECORDS")
        kw.setdefault("precision", DTYPE)  # env default; callers may override
        super().__init__(**kw)

    def build_train_dataset(self):
        tfm = train_transform(self.image_size, seed=self.seed, ship_uint8=_ship_uint8())
        if self.train_records:
            from distributed_training_pytorch_tpu.data import NativeRecordTrainSource, native

            if (
                _ship_uint8()
                and native.available()
                and os.environ.get("RECORDS_NATIVE", "1") != "0"
            ):
                # The full native batch path: decode + random-resized-crop +
                # flip FUSED in one C++ call per batch, uint8 to the device
                # (InputNormalizer). Falls through to the per-record Python
                # pipeline when the native lib (or uint8 ship) is off.
                source = NativeRecordTrainSource(
                    self.train_records, self.image_size, self.image_size,
                    aug="rrc", seed=self.seed,
                )
            else:
                source = RecordFileSource(self.train_records, transform=tfm)
        else:
            self.log("IMAGENET_RECORDS unset — synthetic ImageNet-shaped data", "warning")
            source = synthetic_source(8192, self.image_size, self.num_classes, tfm, seed=0)
        cap = os.environ.get("STEPS_PER_EPOCH")
        if cap:
            source = _LimitedSource(source, int(cap) * self.batch_size)
        return source

    def build_val_dataset(self):
        if self.val_records:
            # Native batch path: record payloads decode+resize+normalize in
            # one C++ call (data/records.NativeRecordFileSource); falls back
            # to the per-record Python pipeline without the native lib.
            from distributed_training_pytorch_tpu.data import NativeRecordFileSource

            return NativeRecordFileSource(
                self.val_records, height=self.image_size, width=self.image_size
            )
        tfm = eval_transform(self.image_size)
        return synthetic_source(1024, self.image_size, self.num_classes, tfm, seed=1)

    def build_model(self):
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        model = create_model(
            self.model_name,
            num_classes=self.num_classes,
            dtype=model_dtype_for_entry(
                self.precision, DTYPE is not None or self.precision_requested, jnp.bfloat16
            ),
            pallas=PALLAS,
        )
        if _ship_uint8():
            from distributed_training_pytorch_tpu.models.wrappers import InputNormalizer

            model = InputNormalizer(
                inner=model, mean=list(T.IMAGENET_MEAN), std=list(T.IMAGENET_STD)
            )
        return model

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
        if self.recipe["optimizer"] == "sgd":
            lr = self.base_lr * self.batch_size / 256.0  # Goyal et al. scaling
        else:
            lr = self.base_lr * self.batch_size / 4096.0  # AdamW convention
        return warmup_cosine_lr(lr, self.max_epoch, steps_per_epoch, warmup_epochs=5)

    def build_optimizer(self, schedule):
        if self.recipe["optimizer"] == "sgd":
            return optax.chain(
                optax.add_decayed_weights(self.recipe["wd"]),
                optax.sgd(schedule, momentum=0.9),
            )
        return optax.adamw(schedule, weight_decay=self.recipe["wd"], b1=0.9, b2=0.999)


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    enable_fast_rng()
    Trainer.distributed_setup()
    model_name = os.environ.get("MODEL", "resnet50").lower()
    if model_name not in RECIPES:
        raise SystemExit(f"MODEL={model_name!r}: choose from {sorted(RECIPES)}")
    recipe = RECIPES[model_name]
    save_dir = os.environ.get("SAVE_DIR", f"./runs/{model_name}")
    trainer = ImageNetTrainer(
        model_name=model_name,
        image_size=int(os.environ.get("IMAGE_SIZE", "224")),
        base_lr=float(os.environ.get("BASE_LR", str(recipe["base_lr"]))),
        max_epoch=int(os.environ.get("EPOCHS", "90")),
        batch_size=int(os.environ.get("BATCH", "1024")),
        chain_steps=int(os.environ.get("CHAIN_STEPS", "1")),
        # MESH (the CHAIN_STEPS/DTYPE convention): a mesh spec like
        # "fsdp4x2" or "dp2fsdp2tp2" trains sharded end to end
        # (docs/parallelism.md); unset = the historical pure-DP program.
        mesh=mesh_from_env(),
        # TELEMETRY=1 (mirrors DTYPE/CHAIN_STEPS): telemetry subsystem —
        # docs/observability.md. Unset = historical program.
        telemetry=os.environ.get("TELEMETRY") == "1" or None,
        accum_steps=int(os.environ.get("ACCUM", str(recipe["accum"]))),
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=1,
        save_folder=save_dir,
        snapshot_path=os.environ.get("SNAPSHOT") or None,
        logger=Logger(f"imagenet-{model_name}", os.path.join(save_dir, "logfile.log")),
        profile=os.environ.get("PROFILE_DIR") or None,
    )
    trainer.train()
    Trainer.destroy_process()
