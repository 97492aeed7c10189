"""``examples/train_lm.py``'s ``LMTrainer`` with the configuration's
vocabulary and token windows from the seed. Everything else is the entry's:
bf16 model casts, fused tied cross-entropy, AdamW (0.1, 0.9, 0.95), the
warm-up + cosine schedule, attention on auto."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.systems.common import StepLosses, trainer_kwargs
from distributed_training_pytorch_tpu.trainer import Trainer
from examples import train_lm


class BenchLMTrainer(StepLosses, train_lm.LMTrainer):
    def __init__(self, windows, dims: dict, seq_len: int, base_lr: float, **kw):
        # LMTrainer.__init__ minus its corpus read (load_windows takes a file
        # or a fixed synthetic stream; the benchmark's windows come from --seed).
        self.seq_len, self.base_lr, self.size, self.moe_every = seq_len, base_lr, "small", 0
        self.dims = dims
        self.windows = windows
        kw.setdefault("precision", train_lm.DTYPE)
        Trainer.__init__(self, **kw)

    def build_model(self):
        from distributed_training_pytorch_tpu.models.transformer_lm import TransformerLM
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        # GPTSmall(...) spelled out, so the widths come from the configuration file
        return TransformerLM(
            **self.dims,
            dtype=model_dtype_for_entry(
                self.precision, train_lm.DTYPE is not None or self.precision_requested, jnp.bfloat16
            ),
            moe_every=0,
            max_len=max(self.seq_len, 128),
            pallas=train_lm.PALLAS,
        )


def prepare() -> None:
    from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng

    enable_fast_rng()  # as the entry's __main__ does


def build(cfg: dict, traffic: dict, data: dict, **common):
    dims = dict(vocab_size=cfg["vocab_size"], hidden_dim=cfg["n_embd"], depth=cfg["n_layer"],
                num_heads=cfg["n_head"], mlp_dim=cfg["n_inner"])
    return BenchLMTrainer(
        data["windows"], dims, traffic["seq_len"], cfg["optimizer"]["lr"],
        **trainer_kwargs(cfg, traffic, **common),
    )


def expect_kernels(cfg: dict, on_tpu: bool) -> list[str]:
    """Failures of what the cell's `why` promises about the path taken."""
    from distributed_training_pytorch_tpu.ops import dispatch

    if not on_tpu:  # off the chip (tests) auto resolves to plain, or PALLAS=1 forces the interpreted kernel
        return []
    recs = [r for r in dispatch.records() if r["model"] == "transformer_lm" and r["op"] == "attention"]
    if recs and all(r["path"] == "flash" for r in recs):
        return []
    return [f"attention left on auto did not resolve to flash: {recs}"]
