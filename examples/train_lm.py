"""Causal-LM training entry — the long-context family's example surface.

Beyond the reference's scope (vision-only); demonstrates the decoder stack
(flash attention on TPU, optional MoE blocks) through the same nine-hook
Trainer the vision configs use. Input is a byte-level corpus file split into
fixed windows (``LM_CORPUS``); without one, a synthetic structured byte stream
keeps the entry smoke-runnable anywhere.

Launch: ``MODEL=lm ./run.sh``. Env knobs: ``LM_CORPUS`` (text/bytes file —
build a real one offline with ``examples/make_lm_corpus.py``), ``SEQ_LEN``
(default 256), ``EPOCHS``, ``BATCH``, ``BASE_LR``, ``MOE_EVERY`` (0 = dense),
``SAVE_DIR``, ``SNAPSHOT``, ``PROFILE_DIR``, ``LM_SIZE`` (``tiny`` | ``small``
= GPT-2-small shape | ``hybrid_tiny`` = a toy of the Mamba-2 / grouped-query
hybrid stack of ``models/hybrid_lm.py``, every block rematerialised |
``nemotron_h_tiny`` = a toy of the same file's one-mixer-a-layer stack with
routed experts),
``SAVE_PERIOD`` / ``LAST_SAVE_PERIOD`` (epochs between periodic / `last`
saves — raise both when the checkpoint path is slow), ``DTYPE``
(fp32|bf16|fp16 mixed-precision policy — docs/mixed_precision.md),
``PALLAS`` (1|0 kernel-policy knob: forces the flash-attention path on/off;
unset = the historical auto — ops/dispatch.py, docs/performance.md
"Autotuning").
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.models import GPTSmall, HybridTiny, LMTiny, NemotronHTiny
from distributed_training_pytorch_tpu.ops import warmup_cosine_lr
from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env
from distributed_training_pytorch_tpu.parallel import mesh_from_env
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache
from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng


def load_windows(seq_len: int, path: str | None = None) -> np.ndarray:
    """[N, seq_len+1] int32 byte windows (input = [:-1], target = [1:]).
    ``path`` overrides the LM_CORPUS env (offline eval passes it directly)."""
    path = path if path is not None else os.environ.get("LM_CORPUS")
    if path:
        if not os.path.exists(path):
            # A typo'd path must not silently train on synthetic data.
            raise FileNotFoundError(f"LM_CORPUS={path!r} does not exist")
        data = np.frombuffer(open(path, "rb").read(), dtype=np.uint8)
    else:
        print("WARNING: LM_CORPUS unset — synthetic structured byte stream")
        rng = np.random.RandomState(0)
        # Repeating motifs + noise: learnable next-byte structure.
        motifs = [rng.randint(0, 255, size=(m,)) for m in (5, 9, 13)]
        parts = [motifs[rng.randint(3)] for _ in range(60000)]
        data = np.concatenate(parts).astype(np.uint8)
    if len(data) < seq_len + 1:
        raise ValueError(
            f"corpus has {len(data)} bytes — too short for SEQ_LEN={seq_len} "
            "(need at least seq_len + 1)"
        )
    # One vectorized strided pass (a per-window Python loop costs tens of
    # seconds and a large transient at GB-corpus scale).
    windows = np.lib.stride_tricks.sliding_window_view(data, seq_len + 1)[::seq_len]
    return windows.astype(np.int32)


# DTYPE (mirrors CHAIN_STEPS): fp32|bf16|fp16 — mixed-precision policy +
# model compute dtype together (fp16 auto-enables dynamic loss scaling;
# docs/mixed_precision.md). Unset keeps the historical program: bf16
# model-internal casts under the default (inactive) fp32 policy. Model dtype
# resolves against the trainer's RESOLVED policy (model_dtype_for_entry) so
# an explicit precision= ctor override agrees with build_model.
DTYPE = os.environ.get("DTYPE") or None

# PALLAS (mirrors DTYPE/CHAIN_STEPS/MESH): 1 forces the Pallas flash-attention
# path, 0 forces the plain einsum path, unset = the historical auto (flash on
# TPU above the sequence-length floor). Every resolution is recorded as a
# kernel_dispatch event (ops/dispatch.py).
PALLAS = pallas_from_env()


class LMTrainer(Trainer):
    def __init__(self, seq_len: int, base_lr: float, size: str, moe_every: int, **kw):
        self.seq_len = seq_len
        self.base_lr = base_lr
        self.size = size
        self.moe_every = moe_every
        self.windows = load_windows(seq_len)
        kw.setdefault("precision", DTYPE)  # env default; callers may override
        super().__init__(**kw)

    # tokens ride the loader's "image" slot; targets are the shifted window.
    def build_train_dataset(self):
        w = self.windows[: int(len(self.windows) * 0.95)]
        return ArrayDataSource(image=w[:, :-1], label=w[:, 1:])

    def build_val_dataset(self):
        w = self.windows[int(len(self.windows) * 0.95) :]
        return ArrayDataSource(image=w[:, :-1], label=w[:, 1:])

    def build_model(self):
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        dtype = model_dtype_for_entry(
            self.precision, DTYPE is not None or self.precision_requested, jnp.bfloat16
        )
        hybrid = {"hybrid_tiny": HybridTiny, "nemotron_h_tiny": NemotronHTiny}.get(self.size)
        if hybrid is not None:  # no positions and no `moe_every` to size
            return hybrid(vocab_size=256, dtype=dtype, pallas=PALLAS)
        factory = {"tiny": LMTiny, "small": GPTSmall}[self.size]
        return factory(
            vocab_size=256,
            dtype=dtype,
            moe_every=self.moe_every,
            max_len=max(self.seq_len, 128),
            pallas=PALLAS,
        )

    criterion_uses_mask = True

    def _aggregate_epoch_metrics(self, host, synced=0):
        """Where the step's metrics hold the expert layers' routing counts
        (``make_fused_lm_loss``: a stack that sows them), the pairs held here
        also go to the ``moe.pairs_local`` counter (``profiling.trace.count``; a
        no-op without a recorder): the epoch's sum, as its steps' metrics reach
        the host."""
        if "moe_pairs_local" in host[0]:
            from distributed_training_pytorch_tpu.profiling.trace import count

            count("moe.pairs_local", float(sum(m["moe_pairs_local"] for m in host)))
        return super()._aggregate_epoch_metrics(host, synced)

    def build_criterion(self):
        def criterion(logits, batch):
            targets = batch["label"]  # [B, T]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            per_example = jnp.mean(nll, axis=-1)  # [B]
            mask = batch.get("mask")
            if mask is None:
                loss = jnp.mean(per_example)
            else:
                loss = jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss, {"nll": loss, "ppl": jnp.exp(loss)}

        return criterion

    def build_loss_fn(self):
        """Fused tied-head CE by default (FUSED_CE=0 for the naive path): the
        model returns final hidden states and ``tied_cross_entropy_loss``
        scans them a slice of the sequence at a time, taking the head's
        gradients in the forward pass — the [B, T, 256]/[B, T, 50257] float32
        logits never materialize (doubles the trainable batch for GPT-small on v5e:
        B=32 -> 64 at T=1024, same tok/s)."""
        if os.environ.get("FUSED_CE", "1") == "0":
            if self.moe_every > 0:
                # the naive criterion path cannot see the routers' sown aux
                # losses — training MoE without them collapses routing, so
                # the toggle is ignored rather than silently degrading
                self.log(
                    "FUSED_CE=0 ignored: MoE models need the fused loss "
                    "(router aux losses ride it)",
                    "warning",
                )
            else:
                return super().build_loss_fn()
        from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss

        return make_fused_lm_loss(self.model)

    def build_scheduler(self):
        steps_per_epoch = max(1, len(self.train_dataset) // self.batch_size)
        return warmup_cosine_lr(self.base_lr, self.max_epoch, steps_per_epoch, warmup_epochs=1)

    def build_optimizer(self, schedule):
        return optax.adamw(schedule, weight_decay=0.1, b1=0.9, b2=0.95)

    def build_example_input(self):
        return jnp.zeros((1, self.seq_len), jnp.int32)


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    enable_fast_rng()
    Trainer.distributed_setup()
    save_dir = os.environ.get("SAVE_DIR", "./runs/lm")
    trainer = LMTrainer(
        seq_len=int(os.environ.get("SEQ_LEN", "256")),
        base_lr=float(os.environ.get("BASE_LR", "3e-4")),
        size=os.environ.get("LM_SIZE", "small"),
        moe_every=int(os.environ.get("MOE_EVERY", "0")),
        max_epoch=int(os.environ.get("EPOCHS", "10")),
        batch_size=int(os.environ.get("BATCH", "256")),
        chain_steps=int(os.environ.get("CHAIN_STEPS", "1")),
        # MESH (the CHAIN_STEPS/DTYPE convention): a mesh spec like
        # "fsdp4x2" or "dp2fsdp2tp2" trains sharded end to end
        # (docs/parallelism.md); unset = the historical pure-DP program.
        mesh=mesh_from_env(),
        # TELEMETRY=1 (mirrors DTYPE/CHAIN_STEPS): telemetry subsystem —
        # docs/observability.md. Unset = historical program.
        telemetry=os.environ.get("TELEMETRY") == "1" or None,
        have_validate=True,
        save_best_for=("nll", "leq"),
        save_period=int(os.environ.get("SAVE_PERIOD", "1")),
        last_save_period=int(os.environ.get("LAST_SAVE_PERIOD", "1")),
        save_folder=save_dir,
        snapshot_path=os.environ.get("SNAPSHOT") or None,
        logger=Logger("lm", os.path.join(save_dir, "logfile.log")),
        profile=os.environ.get("PROFILE_DIR") or None,
    )
    trainer.train()
    Trainer.destroy_process()
