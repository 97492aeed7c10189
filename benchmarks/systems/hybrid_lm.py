"""``examples/train_lm.py``'s ``LMTrainer`` around ``models/hybrid_lm.py``'s
stack at the configuration file's widths, as ``systems/lm.py`` puts it around
the GPT-2 stack: token windows from the seed, the entry's bf16 model casts,
fused tied cross-entropy, AdamW (0.1, 0.9, 0.95), warm-up + cosine, attention
on auto. The configuration's ``memory.remat`` says whether the blocks are
rematerialised. On a commit without ``models/hybrid_lm.py`` the import below
fails and ``run.py`` exits 2 with no result line."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.systems.common import trainer_kwargs
from benchmarks.systems.lm import BenchLMTrainer, prepare  # noqa: F401  (prepare: the entry's fast RNG)
from distributed_training_pytorch_tpu.models.hybrid_lm import HybridConfig, HybridLM
from examples import train_lm


class BenchHybridTrainer(BenchLMTrainer):
    def build_model(self):
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        dtype = model_dtype_for_entry(self.precision, train_lm.DTYPE is not None or self.precision_requested, jnp.bfloat16)
        return HybridLM(self.dims["cfg"], dtype=dtype, pallas=train_lm.PALLAS, remat=self.dims["remat"])


def build(cfg: dict, traffic: dict, data: dict, **common):
    remat = cfg["memory"]["remat"]
    if remat not in ("block", "none"):
        raise ValueError(f"memory.remat {remat!r}: want 'block' or 'none'")
    dims = {"cfg": HybridConfig.from_dict(cfg), "remat": remat == "block"}
    return BenchHybridTrainer(data["windows"], dims, traffic["seq_len"], cfg["optimizer"]["lr"],
                              **trainer_kwargs(cfg, traffic, **common))


def expect_kernels(cfg: dict, on_tpu: bool) -> list[str]:
    """Failures of what the cell's `why` promises about the path taken."""
    from distributed_training_pytorch_tpu.ops import dispatch

    if not on_tpu:  # off the chip (tests) auto resolves to plain, or PALLAS=1 forces the interpreted kernel
        return []
    recs = [r for r in dispatch.records() if r["model"] == "hybrid_lm" and r["op"] == "attention"]
    if recs and all(r["path"] == "flash" for r in recs):
        return []
    return [f"attention left on auto did not resolve to flash: {recs}"]
