"""VGG16 / CIFAR-10 training — the BASELINE.json north-star config.

``./run.sh`` runs this on TPU: VGG16 (bf16 activations) on CIFAR-10 with
data-parallel sharding over every available chip, targeting GPU-DDP top-1
parity at >= 60% MFU (BASELINE.json). Reads the standard ``cifar-10-batches-py``
pickle directory (pure numpy — no torchvision dependency); if absent, falls
back to a synthetic CIFAR-shaped set so the pipeline is still exercisable.

Env knobs: ``CIFAR10_DIR`` (default ./data/cifar-10-batches-py), ``EPOCHS``
(default 100), ``BATCH`` (global, default 1024), ``BASE_LR`` (default 0.1,
linearly scaled by BATCH/256), ``SAVE_DIR`` (default ./runs/cifar10),
``DTYPE`` (fp32|bf16|fp16 mixed-precision policy — docs/mixed_precision.md),
``PALLAS`` (1|0 kernel-policy knob, unset = per-model auto — ops/dispatch.py).
"""

from __future__ import annotations

import os
import pickle
import sys

sys.path.insert(0, ".")

import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.ops import accuracy, cross_entropy_loss, warmup_cosine_lr
from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env
from distributed_training_pytorch_tpu.parallel import mesh_from_env
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache

CIFAR_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)


def load_cifar10(data_dir: str):
    """Read the canonical CIFAR-10 python pickles -> (train_x, train_y, test_x,
    test_y) as uint8 NHWC / int32. Synthetic fallback when the dir is absent."""
    if os.path.isdir(data_dir):
        def read(name):
            with open(os.path.join(data_dir, name), "rb") as f:
                d = pickle.load(f, encoding="bytes")
            x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
            y = np.asarray(d[b"labels"], np.int32)
            return x, y

        xs, ys = zip(*(read(f"data_batch_{i}") for i in range(1, 6)), strict=True)
        test_x, test_y = read("test_batch")
        return np.concatenate(xs), np.concatenate(ys), test_x, test_y
    print(f"WARNING: {data_dir} not found — using synthetic CIFAR-shaped data")
    rng = np.random.RandomState(0)
    y = rng.randint(0, 10, size=(50000,)).astype(np.int32)
    x = (rng.randn(50000, 32, 32, 3) * 40 + 120 + y[:, None, None, None] * 8).clip(0, 255)
    ty = rng.randint(0, 10, size=(10000,)).astype(np.int32)
    tx = (rng.randn(10000, 32, 32, 3) * 40 + 120 + ty[:, None, None, None] * 8).clip(0, 255)
    return x.astype(np.uint8), y, tx.astype(np.uint8), ty


class Cifar10Transform:
    """Standard CIFAR recipe: pad-4 random crop + horizontal flip + normalize,
    deterministic per (epoch, index) like data.transforms.Compose."""

    def __init__(self, seed: int = 0, train: bool = True):
        self.seed = seed
        self.train = train

    def __call__(self, img: np.ndarray, *, epoch: int = 0, index: int = 0) -> np.ndarray:
        from distributed_training_pytorch_tpu.data.transforms import philox_key

        out = img.astype(np.float32) / 255.0
        if self.train:
            rng = np.random.Generator(np.random.Philox(key=philox_key(self.seed, epoch, index)))
            padded = np.pad(out, ((4, 4), (4, 4), (0, 0)), mode="reflect")
            dy, dx = rng.integers(0, 9, size=2)
            out = padded[dy : dy + 32, dx : dx + 32]
            if rng.random() < 0.5:
                out = out[:, ::-1]
        return np.ascontiguousarray((out - CIFAR_MEAN) / CIFAR_STD)


# DTYPE (mirrors CHAIN_STEPS): fp32|bf16|fp16 — sets the trainer's mixed-
# precision policy AND the model compute dtype together (fp16 auto-enables
# dynamic loss scaling; docs/mixed_precision.md). Unset keeps this entry's
# historical program: bf16 model-internal casts under the default (inactive)
# fp32 policy. Model dtype resolves via precision.model_dtype_for_entry
# against the trainer's RESOLVED policy, so an explicit precision= ctor
# override agrees with build_model even when the env knob is unset.
DTYPE = os.environ.get("DTYPE") or None

# PALLAS (mirrors DTYPE/CHAIN_STEPS/MESH): 1 forces the fused Pallas kernel
# paths, 0 forces plain XLA, unset = per-model auto — for VGG16 every
# resolution lands on plain (no fused-kernel coverage for 3x3 convs) and the
# no-op is recorded as a kernel_dispatch event rather than ignored silently
# (ops/dispatch.py).
PALLAS = pallas_from_env()


class Cifar10Trainer(Trainer):
    def __init__(self, data_dir: str, base_lr: float, **kw):
        data = load_cifar10(data_dir)
        self.train_x, self.train_y, self.test_x, self.test_y = data
        self.base_lr = base_lr
        kw.setdefault("precision", DTYPE)  # env default; callers may override
        super().__init__(**kw)

    def _transform(self, train: bool):
        # Prefer the native C++ batch augmenter (one GIL-free call per batch)
        # with uint8 output — normalization runs on device (InputNormalizer),
        # so the H2D link carries 1 byte/px instead of 4. Python per-record
        # fallback normalizes host-side. Both are deterministic per
        # (seed, epoch, record) — see data/native.py.
        from distributed_training_pytorch_tpu.data import native

        if native.available():
            return native.NativeCropFlipU8(pad=4, seed=self.seed, train=train)
        return Cifar10Transform(seed=self.seed, train=train)

    @property
    def _device_normalize(self) -> bool:
        from distributed_training_pytorch_tpu.data import native

        return native.available()

    def build_train_dataset(self):
        return ArrayDataSource(
            transform=self._transform(train=True),
            image=self.train_x,
            label=self.train_y,
        )

    def build_val_dataset(self):
        return ArrayDataSource(
            transform=self._transform(train=False),
            image=self.test_x,
            label=self.test_y,
        )

    def build_model(self):
        from distributed_training_pytorch_tpu.models import create_model
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        # create_model consumes the pallas knob for VGG16 (no fused-kernel
        # coverage) and records the plain resolution — the knob is uniform
        # across entries, never silently dropped.
        model = create_model(
            "vgg16",
            num_classes=10,
            dtype=model_dtype_for_entry(
                self.precision, DTYPE is not None or self.precision_requested, jnp.bfloat16
            ),
            pallas=PALLAS,
        )
        if self._device_normalize:
            from distributed_training_pytorch_tpu.models import InputNormalizer

            model = InputNormalizer(model, mean=tuple(CIFAR_MEAN), std=tuple(CIFAR_STD))
        return model

    # mask-weighted metrics below satisfy the padded-validation contract
    # (trainer.validate warns when this is not declared)
    criterion_uses_mask = True

    def build_criterion(self):
        def criterion(logits, batch):
            mask = batch.get("mask")
            loss = cross_entropy_loss(logits, batch["label"], weights=mask)
            return loss, {
                "ce_loss": loss,
                "accuracy": accuracy(logits, batch["label"], weights=mask),
            }

        return criterion

    def build_optimizer(self, schedule):
        return optax.chain(optax.add_decayed_weights(5e-4), optax.sgd(schedule, momentum=0.9))

    def build_scheduler(self):
        steps_per_epoch = max(1, len(self.train_y) // self.batch_size)
        # Linear LR scaling with global batch (Goyal et al. recipe) + cosine.
        lr = self.base_lr * self.batch_size / 256.0
        return warmup_cosine_lr(lr, self.max_epoch, steps_per_epoch, warmup_epochs=5)


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    Trainer.distributed_setup()
    save_dir = os.environ.get("SAVE_DIR", "./runs/cifar10")
    trainer = Cifar10Trainer(
        data_dir=os.environ.get("CIFAR10_DIR", "./data/cifar-10-batches-py"),
        base_lr=float(os.environ.get("BASE_LR", "0.1")),
        max_epoch=int(os.environ.get("EPOCHS", "100")),
        batch_size=int(os.environ.get("BATCH", "1024")),
        chain_steps=int(os.environ.get("CHAIN_STEPS") or 1),
        # MESH (the CHAIN_STEPS/DTYPE convention): a mesh spec like
        # "fsdp4x2" or "dp2fsdp2tp2" trains sharded end to end
        # (docs/parallelism.md); unset = the historical pure-DP program.
        mesh=mesh_from_env(),
        # TELEMETRY=1 (mirrors DTYPE/CHAIN_STEPS): telemetry subsystem —
        # docs/observability.md. Unset = historical program.
        telemetry=os.environ.get("TELEMETRY") == "1" or None,
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=5,
        save_folder=save_dir,
        snapshot_path=os.environ.get("SNAPSHOT") or None,
        logger=Logger("cifar10-vgg16", os.path.join(save_dir, "logfile.log")),
    )
    trainer.train()
    Trainer.destroy_process()
