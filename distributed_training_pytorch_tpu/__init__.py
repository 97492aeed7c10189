"""distributed_training_pytorch_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA re-design of the capability surface of
``ducphuongbk01/Distributed-Training-Pytorch`` (reference: ``trainer/trainer.py``,
``example_trainer.py``, ``model/vgg16.py``, ``dataset/example_dataset.py``,
``utils/logger.py``, ``main.py``, ``eval.py``, ``run.sh``): a template-method
trainer with user-overridable hooks, multi-host data-parallel training,
epoch-based orchestration with periodic validation, best/last/periodic
checkpointing with snapshot resume, file+console logging, and a standalone
offline evaluator — rebuilt TPU-first:

* ``parallel``  — device-mesh bootstrap (``jax.distributed`` + ``jax.sharding.Mesh``),
  sharding rules (FSDP / Megatron-TP), ring + Ulysses sequence parallelism,
  GPipe-style pipeline parallelism, GShard-style MoE expert parallelism.
* ``models``    — Flax model zoo (VGG16, ResNet-50, ViT-B/16, ConvNeXt-L).
* ``ops``       — losses, metrics, schedules, Pallas kernels.
* ``train``     — functional ``TrainState`` + jitted train/eval step engine
  (replaces DDP + criterion/optimizer/scheduler mutation).
* ``precision`` — mixed-precision dtype policies (fp32/bf16/fp16 with fp32
  master weights) + dynamic loss scaling as on-device pytree state
  (docs/mixed_precision.md).
* ``data``      — deterministic host-sharded input pipeline with device prefetch
  (replaces ``DistributedSampler`` + ``DataLoader``).
* ``checkpoint``— Orbax-backed best/last/periodic checkpointing with resume,
  crash-consistent atomic commits, integrity validation, and newest-valid
  fallback (docs/fault_tolerance.md).
* ``fault``     — fault-injection harness (``FaultPlan``) + hung-step
  watchdog: preemption, torn saves, NaN steps, and corrupt records as
  tested code paths.
* ``telemetry`` — run observability: structured JSONL event log, goodput
  wall-time buckets (cumulative across kill/resume), on-device train-health
  stats, MFU/roofline fields, anomaly detectors (docs/observability.md).
* ``analysis``  — static analysis: jaxlint (project-specific AST rules with
  audited inline waivers), compiled-program HLO audit (donation aliasing,
  precision leaks, host callbacks), generic ruff/stdlib layer
  (docs/static_analysis.md; gate: ``scripts/static_audit.py``).
* ``memory``    — memory observability: per-buffer HBM attribution from
  ``compiled.memory_analysis()`` (class fractions sum to 1), the OOM
  preflight with batch/microbatch recommendations
  (``Trainer(preflight=...)``), shared live ``memory_stats`` telemetry +
  growth detection (docs/memory.md; gate: ``scripts/memory_probe.py``).
* ``compat``    — ``force_host_devices``: the virtual multi-device CPU rig
  tests and CPU harnesses share (no version shims: jax 0.9.0 only).
* ``trainer``   — the epoch-loop orchestrator with the reference's 9 hook names.
* ``utils``     — logging, profiling/tracing (``utils.profiling``), TPU perf
  defaults (``utils.tpu``).
"""

__version__ = "0.2.0"

from distributed_training_pytorch_tpu.checkpoint import (  # noqa: F401
    CheckpointError,
    CheckpointManager,
    CorruptCheckpointError,
)
from distributed_training_pytorch_tpu.fault import (  # noqa: F401
    FaultPlan,
    StepWatchdog,
)
from distributed_training_pytorch_tpu.parallel.mesh import (  # noqa: F401
    setup_distributed,
    create_mesh,
    shutdown_distributed,
)
from distributed_training_pytorch_tpu.precision import (  # noqa: F401
    DynamicScale,
    NoOpScale,
    Policy,
)
from distributed_training_pytorch_tpu.telemetry import (  # noqa: F401
    AnomalyDetector,
    Telemetry,
)
