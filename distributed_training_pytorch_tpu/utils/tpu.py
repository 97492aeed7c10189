"""TPU performance defaults.

One documented switch instead of the reference's NCCL env tuning block
(``/root/reference/run.sh:1-8`` — NCCL_ALGO/PROTO/P2P_LEVEL etc.): on TPU the
XLA compiler owns scheduling and collective selection, so the only knob worth
setting globally is the PRNG implementation.
"""

from __future__ import annotations

import jax

__all__ = ["enable_fast_rng", "tpu_compiler_options"]


def tpu_compiler_options() -> dict:
    """Per-compile XLA:TPU options worth setting for conv-heavy steps.

    ``xla_tpu_scoped_vmem_limit_kib=49152``: raises the compiler's scoped-VMEM
    budget from its ~16MB default so conv/weight prefetch fusions double-buffer
    deeper. Its effect is not measured on today's chip (ROADMAP S2(b)); the
    installed libtpu accepts the option (``chip_smoke.py`` compiles with it).
    Pass to ``TrainEngine.compile_train_step(compiler_options=...)``
    (per-compile, so a CPU process never sees a TPU-only flag).
    Returns {} on non-TPU backends.
    """
    if jax.default_backend() != "tpu":
        return {}
    return {"xla_tpu_scoped_vmem_limit_kib": "49152"}


def enable_fast_rng() -> None:
    """Use the hardware RBG-based PRNG for ``jax.random`` keys.

    JAX's default ``threefry2x32`` is counter-based and fully reproducible
    across backends, but costs real MXU/VPU time when a train step draws large
    dropout masks every step (cost not measured on today's chip).
    ``rbg`` keys use the TPU's hardware random-bit generator: same
    (key, shape) -> bits determinism within a backend, much cheaper to
    generate.

    Call before any ``jax.random.key`` creation (typically first thing in a
    train script). Tests keep the default threefry for cross-platform
    reproducibility.
    """
    jax.config.update("jax_default_prng_impl", "rbg")
