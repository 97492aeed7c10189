"""The epoch-loop orchestrator — the framework's user-facing core.

Capability twin of the reference ``trainer/trainer.py`` abstract ``Trainer``:
the same template-method surface (the nine user hooks, ``trainer/trainer.py:
219-253``), the same constructor contract (``:15-24``), the same epoch loop —
resume-aware range (``:110``), periodic validation with best-model tracking
(``:114-135``), per-epoch train loop with progress bar and loss collection
(``:138-156``), scheduler reporting (``:159-160``), last/periodic
checkpointing (``:163-172``), mean-loss logging (``:175-178``) — rebuilt on a
functional core:

* mutable ``self.model/optimizer/scheduler`` become one :class:`TrainState`
  pytree threaded through a jitted step (``train.engine.TrainEngine``);
* DDP + NCCL barriers disappear: the batch is sharded over the mesh's ``data``
  axis, XLA inserts and overlaps the gradient all-reduce, and checkpoint saves
  are collective (Orbax), so there is no rank-0 barrier choreography;
* validation is *collective* (every device evaluates a shard) instead of the
  reference's rank-0-only full-dataset pass (``:184-206``, SURVEY.md §2e), and
  reported metrics are global means, not per-rank locals;
* the scheduler is an optax per-step schedule fused into the optimizer, so
  "scheduler state" is just ``state.step``.

Hook mapping (reference -> here):

=================  ==========================================================
``build_train_dataset``  same name; returns an indexable source (may carry a
                         ``.transform`` applied by the loader)
``build_val_dataset``    same (fixed to default to *val* data, §2e bug)
``build_model``          same; returns a Flax module
``build_criterion``      same; returns ``(outputs, batch) -> (loss, metrics)``
``build_optimizer``      same; receives the schedule, returns an optax
                         ``GradientTransformation``
``build_scheduler``      same; returns an optax per-step ``Schedule`` or a
                         constant lr
``preprocess_batch``     same; host-side, before device transfer (the H2D copy
                         itself is the framework's job now)
``train_step``           same name; ``(state, batch) -> (state, metrics)`` —
                         default delegates to the compiled engine step
``validate_step``        same name; ``(state, batch) -> metrics`` — default is
                         the compiled collective eval step
=================  ==========================================================
"""

from __future__ import annotations

import dataclasses
import functools
import os
import signal
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu.checkpoint import (
    BEST,
    LAST,
    CheckpointError,
    CheckpointManager,
    epoch_checkpoint_name,
)
from distributed_training_pytorch_tpu.data import ShardedLoader, epoch_units
from distributed_training_pytorch_tpu.fault.watchdog import StepWatchdog
from distributed_training_pytorch_tpu.memory import resolve_preflight, run_preflight
from distributed_training_pytorch_tpu.parallel import elastic as elastic_lib
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.precision import (
    get_policy,
    is_dynamic,
    resolve_loss_scale,
)
from distributed_training_pytorch_tpu.profiling import (
    StepTraceCapture,
    annotate,
    install_recorder,
    resolve_profile,
)
from distributed_training_pytorch_tpu.resilience import AsyncCheckpointSaver
from distributed_training_pytorch_tpu.telemetry import resolve_telemetry
from distributed_training_pytorch_tpu.telemetry.run import RunTelemetry
from distributed_training_pytorch_tpu.train import (
    NonFiniteLossError,
    TrainEngine,
    make_supervised_loss,
)
from distributed_training_pytorch_tpu.utils.tensorboard import MetricsWriter


def _init_span(init):
    """``Trainer.__init__`` as one ``trainer.init`` span. The span recorder
    (profiling/trace.py) is installed first, exactly when telemetry is on: it
    is process-wide, because the loader workers and the prefetch thread are
    not handed the trainer."""

    @functools.wraps(init)
    def wrapped(self, *args, telemetry=None, **kwargs):
        if resolve_telemetry(telemetry) is not None:
            install_recorder()
        with annotate("trainer.init"):
            init(self, *args, telemetry=telemetry, **kwargs)

    return wrapped


class Trainer:
    """Subclass, implement the hooks, call :meth:`train`.

    Constructor args mirror ``trainer/trainer.py:15-24``; ``pin_memory`` is
    accepted for source compatibility but ignored (device transfer is async
    via the prefetcher — there is no pageable/pinned distinction to manage).
    """

    @_init_span
    def __init__(
        self,
        max_epoch: int,
        batch_size: int,
        pin_memory: bool = False,
        have_validate: bool = False,
        save_best_for: tuple[str, str] | None = None,
        save_period: int | None = None,
        save_folder: str = ".",
        snapshot_path: str | None = None,
        logger=None,
        *,
        mesh: jax.sharding.Mesh | None = None,
        sharding_rules="auto",
        fsdp_min_size: int = 2**18,
        seed: int = 0,
        accum_steps: int = 1,
        num_workers: int = 8,
        prefetch_batches: int = 2,
        log_every: int = 50,
        chain_steps: int = 1,
        last_save_period: int = 1,
        async_checkpoint: bool = True,
        progress: bool = True,
        save_on_preemption: bool = True,
        preemption_check_every: int = 20,
        max_checkpoints_to_keep: int | None = None,
        tensorboard_dir: str | None = None,
        nan_policy: str | None = None,
        skip_corrupt_records: bool = False,
        step_timeout: float | None = None,
        fault_plan=None,
        precision=None,
        loss_scale=None,
        telemetry=None,
        profile=None,
        preflight=None,
    ):
        # Logger closure — exact contract of ``trainer/trainer.py:26``.
        self.log = (
            (lambda msg, log_type="info": logger.log(msg, log_type))
            if logger is not None
            else (lambda msg, log_type="info": print(f"{log_type.upper()}: {msg}"))
        )

        self.max_epoch = max_epoch
        self.batch_size = batch_size
        self.have_validate = have_validate
        self.save_best_for = save_best_for
        self.save_period = save_period
        self.seed = seed
        self.accum_steps = accum_steps
        self.num_workers = num_workers
        # Host-side batch look-ahead (ShardedLoader window); the device-side
        # ring (data.epoch_units, depth 2) bounds on-device staging.
        self.prefetch_batches = prefetch_batches
        self.log_every = log_every
        # The reference saves `last` every epoch (``trainer/trainer.py:163``)
        # — the right default on local disk. When the checkpoint path is slow
        # (multi-GB states), raise this to save `last` every N epochs;
        # preemption saves still fire regardless.
        self.last_save_period = max(1, int(last_save_period))
        self.cur_epoch = 0
        # `profile=` (a profiling.ProfileConfig or a trace-dir string;
        # docs/profiling.md) traces a window of the REAL execution, chained
        # windows included, and analyzes it into a StepProfile; the run's
        # numbers and trace counts are those of profile=None.
        self.profile = resolve_profile(profile)
        if self.profile is not None and self.profile.dir is None:
            self.profile = dataclasses.replace(
                self.profile, dir=os.path.join(save_folder, "profile")
            )
        self.progress = progress
        # SIGTERM (what cloud schedulers send ahead of eviction, to every
        # host of the job) sets a flag the step loop polls; the loop then
        # saves a resumable snapshot and returns. The handler only flips the
        # flag: saves are collective and must not run in signal context.
        self._preempted = False
        self._epoch_interrupted = False
        self._prev_sigterm = None
        self._sigterm_installed = False
        self.save_on_preemption = save_on_preemption
        # Multi-host SIGTERM reaction latency bound: every `preemption_check_
        # every` steps all hosts vote (one tiny allgather — the only intra-
        # epoch host sync besides log_every). 0 = epoch boundaries only.
        self.preemption_check_every = preemption_check_every
        # Optional TensorBoard scalars (process 0 only).
        self.metrics_writer = MetricsWriter(tensorboard_dir)

        # nan_policy governs steps whose loss/grads go non-finite:
        #   None                 — train on, no guard;
        #   "raise"              — NonFiniteLossError at the next host sync
        #                          point (log_every / epoch end);
        #   "skip"               — the engine guard drops the update (params
        #                          untouched, step advances), counted in
        #                          self.nonfinite_steps;
        #   "restore_last_good"  — like "skip", plus the state is rolled back
        #                          to the newest VALID checkpoint at the next
        #                          host sync point after a poisoned step.
        if nan_policy not in (None, "raise", "skip", "restore_last_good"):
            raise ValueError(
                f"nan_policy must be None|raise|skip|restore_last_good, got {nan_policy!r}"
            )
        self.nan_policy = nan_policy
        self.nonfinite_steps = 0
        self.nonfinite_rollbacks = 0
        # `precision` names a dtype policy (docs/mixed_precision.md: "fp32"
        # default; "bf16" = fp32 master params + bf16 compute; "fp16" adds
        # dynamic loss scaling). `loss_scale` overrides the scaling choice
        # ("dynamic" | "none" | a precision.DynamicScale/NoOpScale instance;
        # None = policy default). Resolved BEFORE the build hooks so that
        # build_model can read self.model_dtype. precision_requested tells an
        # explicit precision="fp32" from an unset knob (the resolved Policy is
        # the same): entries whose model defaults to another dtype honor the
        # explicit request.
        self.precision_requested = precision is not None
        self.precision = get_policy(precision)
        self._initial_loss_scale = resolve_loss_scale(loss_scale, self.precision)
        if self.precision.compute_dtype == jnp.float16 and not is_dynamic(
            self._initial_loss_scale
        ):
            raise ValueError(
                "precision='fp16' requires dynamic loss scaling (fp16 grads "
                "underflow below ~6e-5 without it): leave loss_scale unset "
                "or pass loss_scale='dynamic'. Use precision='bf16' for "
                "scale-free low precision — bf16 keeps fp32's exponent range."
            )
        if is_dynamic(self._initial_loss_scale) and nan_policy in (
            "raise",
            "restore_last_good",
        ):
            raise ValueError(
                f"nan_policy={nan_policy!r} is incompatible with dynamic loss "
                "scaling: overflow-skip + backoff IS the scale calibration "
                "mechanism — 'raise' would abort normal fp16 training on the "
                "first benign overflow, and 'restore_last_good' would roll "
                "the whole state back to an old checkpoint (undoing the "
                "backoff, so the overflow repeats) every time the scale "
                "probes too high. Use nan_policy=None or 'skip' (skipped "
                "steps are still counted once in nonfinite_steps and "
                "state.loss_scale.skipped_steps)."
            )
        self.skip_corrupt_records = skip_corrupt_records
        # Wall-clock hung-step watchdog: past `step_timeout` seconds without
        # a completed step, SIGTERM ourselves — the preemption handler then
        # turns the hang into a resumable save at the next safe point.
        self.step_timeout = step_timeout
        # The timeout actually armed (step_timeout x chain_steps under
        # chaining — set by train_epoch, reported by _on_hung_step).
        self._watchdog_timeout = step_timeout
        # Deterministic fault injection (tests; None in production).
        self.fault_plan = fault_plan
        # Windows of `chain_steps` train steps dispatch as ONE compiled
        # program (engine.train_steps_chained): no per-step host dispatch.
        # Per-step metrics come back stacked, so loss logging and nonfinite
        # accounting stay exact; the epoch tail, the realignment prefix after
        # a resume, and any window with a fault pending run as single steps
        # (the same arithmetic: tests/test_chained.py states how closely).
        self.chain_steps = int(chain_steps)
        self._validate_chain_config()
        # Mid-epoch resume position (set when restoring a preemption save's
        # loop state; consumed by the first trained epoch).
        self._resume_step_in_epoch = 0
        self._interrupted_at_step = 0

        # Save folder layout: <save_folder>/weights/<name> (``:29-32``). The
        # manager commits synchronously (a save it runs is durable when the
        # call returns); `async_checkpoint=True` routes periodic/best saves
        # through AsyncCheckpointSaver: a device->host snapshot on this
        # thread, the staging+manifest+rename commit on a background thread.
        # Preemption/watchdog saves always commit synchronously.
        self.save_folder = save_folder
        self.save_weight_folder = os.path.join(save_folder, "weights")
        self._async_saves = bool(async_checkpoint)
        self.checkpoints = CheckpointManager(
            self.save_weight_folder,
            save_best_for=save_best_for,
            async_save=False,
            max_to_keep=max_checkpoints_to_keep,
            fault_plan=fault_plan,
        )
        self.saver = AsyncCheckpointSaver(self.checkpoints, on_commit=self._on_async_commit)

        # Everything the run tells telemetry goes through this one object
        # (telemetry/run.py). With telemetry=None it is built disabled: the
        # log is a no-op, the meter None, and the engine traces the step
        # without the health stats. Built BEFORE the mesh so that the resume
        # peek below, which may re-plan the mesh, reports through the log.
        self.run_telemetry = RunTelemetry(
            telemetry, save_folder=save_folder, log=self.log, metrics_writer=self.metrics_writer
        )
        self.events = self.run_telemetry.events
        self.goodput = self.run_telemetry.goodput
        self.anomaly_detector = self.run_telemetry.anomaly_detector
        # Recovery skips (restore_latest_valid / the resume peek walking past
        # a corrupt checkpoint) land in the log as `checkpoint_rejected`.
        self.checkpoints.event_log = self.events

        # Elastic resume (docs/fault_tolerance.md): the resume checkpoint is
        # resolved BEFORE the mesh is chosen. A sharded checkpoint written on
        # another device count re-plans the mesh axes + grad accumulation for
        # this topology (parallel.elastic) when mesh=None: a run killed at
        # fsdp=8 resumes on 4 or 16 devices. The peek reads host-side
        # metadata only; same-topology resumes and cold starts set nothing.
        snapshot_path = self._peek_resume_checkpoint(snapshot_path, mesh, batch_size)
        if self._elastic_plan is not None:
            mesh = self._elastic_plan.mesh_config.build()

        # Mesh — the distributed world (replaces LOCAL_RANK/RANK/WORLD_SIZE
        # env reads + DDP wrap, ``:48-52``). mesh=None is pure data
        # parallelism (1-D data mesh over every device, replicated params);
        # any MeshConfig(...).build() mesh trains sharded end to end
        # (docs/parallelism.md).
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
        self.world_size = self.mesh.devices.size
        # Batch-dim divisibility is against the BATCH-SHARDED axes product
        # (data x fsdp — parallel.mesh.batch_shard_extent), not the device
        # count: a data=2/tensor=4 mesh runs 2 batch shards on 8 devices,
        # and requiring batch % 8 == 0 would reject valid TP configs while
        # batch % 2 != 0 would fail deep in jax array assembly instead of
        # here with names attached.
        self.batch_replicas = mesh_lib.batch_shard_extent(self.mesh)
        if batch_size % self.batch_replicas:
            raise ValueError(
                f"global batch_size {batch_size} is not divisible by the "
                f"mesh's batch-shard extent {self.batch_replicas} (= product "
                "of the data and fsdp axes): every batch shard must hold the "
                "same number of rows. Round batch_size or re-plan the mesh."
            )
        # A resumed run on a re-planned (or hand-picked) mesh can land on a
        # global batch the new data x fsdp extent x accumulation does not
        # tile; the engine's microbatch reshape would then fail deep in jax
        # array assembly, so fail here with names attached.
        if self._topology_changed and batch_size % (
            self.batch_replicas * self.accum_steps
        ):
            suggestion = elastic_lib.nearest_divisible_accum(
                batch_size, self.batch_replicas, self.accum_steps
            )
            raise ValueError(
                f"global batch_size {batch_size} does not tile into "
                f"accum_steps={self.accum_steps} microbatches over the "
                f"resumed mesh's batch-shard extent {self.batch_replicas}: "
                "every microbatch shard must hold the same number of rows "
                f"(batch % (extent x accum) != 0). Nearest divisible "
                f"accum_steps: {suggestion}."
            )
        self.local_batch_size = batch_size // jax.process_count()
        # Parameter-sharding rules (parallel.sharding): "auto" resolves via
        # the build_sharding_rules hook AFTER build_model runs (the hook may
        # inspect self.model); an explicit list/None passes through. Any OTHER
        # string is rejected here — forwarded to the engine it would crash
        # deep inside state_shardings as a bogus (regex, spec) iterable with
        # no mention of this knob.
        if isinstance(sharding_rules, str) and sharding_rules != "auto":
            raise ValueError(
                f"sharding_rules={sharding_rules!r}: the only string value is "
                "'auto' (resolve via build_sharding_rules). Pass None for the "
                "replicated/FSDP-fallback default, or an explicit list of "
                "(path_regex, PartitionSpec) rules."
            )
        self.fsdp_min_size = int(fsdp_min_size)

        self.run_telemetry.set_mesh(self.mesh)
        # Memory preflight (memory/preflight.py): predict the program's peak
        # HBM from an abstract lowering BEFORE the first real compile and fail
        # fast on predicted OOM with a batch / microbatch recommendation.
        # preflight=None (default): no lowering, no probe.
        self.preflight = resolve_preflight(preflight)
        self._preflight_done = False
        # The last PreflightReport (fit verdict, per-class attribution,
        # recommendations) — operator-inspectable after train().
        self.memory_report = None
        # profiling/capture.py: one traced window of real steps, driven at
        # unit boundaries in train_epoch (process 0).
        self._profile_capture = (
            StepTraceCapture(
                self.profile,
                log=self.log,
                events=self.events,
                flops_source=self._profile_flops_index,
            )
            if self.profile is not None
            else None
        )
        # The first executed batch's per-step shapes (ShapeDtypeStructs): what
        # the preflight, the one-time FLOP probe and the capture's roofline
        # join lower against.
        self._abstract_batch = None

        # Build hooks (``:38-41``) — model/criterion first, then datasets
        # (so ``build_scheduler`` can size per-epoch schedules from
        # ``len(self.train_dataset)`` without re-scanning), then
        # schedule/optimizer/engine.
        self.model = self.build_model()
        self.criterion = self.build_criterion()

        # Datasets + loaders (``:56-71``).
        with annotate("trainer.build_loaders"):
            self.train_dataset = self.build_train_dataset()
            self.train_dataloader = self.build_dataloader(self.train_dataset, phase="train")
        # Streaming data plane (docs/data.md), duck-typed: a loader with
        # ``reader_state`` gets its reader state carried by checkpoints and
        # the shard_assignment / data_reader_state records. Telling it the
        # mesh's batch-shard extent pins its assignment version to the
        # data x fsdp split it feeds, which is what makes an elastic N→M
        # resume visible as a version change.
        self._streaming_train = hasattr(self.train_dataloader, "reader_state")
        if self._streaming_train and hasattr(self.train_dataloader, "batch_extent"):
            self.train_dataloader.batch_extent = self.batch_replicas
        self.val_dataloader = None
        if have_validate:
            with annotate("trainer.build_loaders"):
                self.val_dataset = self.build_val_dataset()
                self.val_dataloader = self.build_dataloader(self.val_dataset, phase="val")

        schedule = self.build_scheduler()
        if schedule is None:
            schedule = optax.constant_schedule(0.0)
        elif not callable(schedule):
            schedule = optax.constant_schedule(float(schedule))
        self.schedule = schedule
        self.optimizer = self.build_optimizer(self.schedule)

        self.sharding_rules = (
            self.build_sharding_rules() if isinstance(sharding_rules, str) else sharding_rules
        )
        self.engine = TrainEngine(
            self.build_loss_fn(),
            self.optimizer,
            self.mesh,
            # self.accum_steps, not the ctor arg: an elastic re-plan may have
            # re-solved the factor for the new batch-shard extent.
            accum_steps=self.accum_steps,
            schedule=self.schedule,
            nan_guard=self.nan_policy in ("skip", "restore_last_good"),
            precision=self.precision,
            loss_scale=self._initial_loss_scale,
            stats=self.run_telemetry.stats,
            sharding_rules=self.sharding_rules,
            fsdp_min_size=self.fsdp_min_size,
        )

        # State init (replaces model.to(device) + DDP param broadcast):
        # init_state jits the model init with the engine's state sharding as
        # OUTPUT sharding, so a model too big for one chip's HBM never exists
        # replicated anywhere.
        example = self.build_example_input()
        with annotate("engine.init_state"):
            self.state = self.engine.init_state(
                jax.random.key(seed),
                lambda rng: self.model.init(rng, example),
            )
        self._log_sharded_layout()

        # Snapshot resume (``:44-45,96-101``). The peek above resolved
        # "latest_valid" to the newest checkpoint passing integrity
        # validation (or to None on a cold start) and read its meta.
        if snapshot_path is not None:
            t_restore = time.perf_counter()
            self.state, self.cur_epoch = self.checkpoints.restore(
                snapshot_path,
                self.state,
                # The peek's latest_valid resolution already hashed every
                # file; re-validating would double the resume disk reads.
                validate=not self._resume_prevalidated,
                # The peek inspected the recorded topology: a mismatch was
                # either re-planned (mesh=None) or explicitly overridden by
                # the user's mesh — both restore into a current-backend
                # layout, so the manager's topology seam may stand down.
                allow_topology_change=self._topology_changed,
            )
            meta = (
                self._resume_meta
                if self._resume_meta is not None
                else self.checkpoints.read_meta(snapshot_path)
            )
            self._resume_step_in_epoch = int((meta.get("loop") or {}).get("step_in_epoch", 0))
            # The checkpoint's data/ item positions a streaming reader: a
            # missing item means a fresh cursor; a present one is validated
            # against this stream and positions at cursor // G. The data
            # cursor is authoritative for the reader; the loop's
            # step_in_epoch (saved atomically with it) cross-checks it.
            if self._streaming_train:
                data_state = self.checkpoints.read_data_state(snapshot_path)
                if data_state:
                    resume_batch = self.train_dataloader.apply_reader_state(data_state)
                    if resume_batch != self._resume_step_in_epoch:
                        self.log(
                            "checkpoint data cursor (batch "
                            f"{resume_batch}) disagrees with loop "
                            f"step_in_epoch ({self._resume_step_in_epoch}); "
                            "trusting the data cursor",
                            "warning",
                        )
                        self._resume_step_in_epoch = resume_batch
                else:
                    self.log(
                        "checkpoint has no data/ item (pre-streaming): "
                        "streaming reader resumes with a fresh cursor"
                    )
            self.run_telemetry.restored(meta, time.perf_counter() - t_restore)
            self.events.emit(
                "checkpoint_restore",
                name=os.path.basename(str(snapshot_path)),
                epoch=self.cur_epoch,
                step_in_epoch=self._resume_step_in_epoch,
            )
            self._emit_elastic_restore(snapshot_path)
            self.log(
                f"Resumed from {snapshot_path} at epoch {self.cur_epoch}"
                + (
                    f", step {self._resume_step_in_epoch} (mid-epoch)"
                    if self._resume_step_in_epoch
                    else ""
                )
            )

    # ------------------------------------------------------------------
    # Framework-provided machinery (overridable, like ``build_dataloader``
    # at ``trainer/trainer.py:209-217``).
    # ------------------------------------------------------------------

    def build_dataloader(self, dataset, phase: str = "train") -> ShardedLoader:
        """Default loader: deterministic global shuffle for train (fixing the
        reference's cross-rank shuffle bug, SURVEY.md §2e), padded static-shape
        final batch for val."""
        train = phase == "train"
        return ShardedLoader(
            dataset,
            self.batch_size,
            shuffle=train,
            seed=self.seed,
            transform=getattr(dataset, "transform", None),
            # dataset.collate_fn (ref trainer/trainer.py:59-71) is picked up
            # by the ShardedLoader ctor's own fallback.
            num_workers=self.num_workers,
            prefetch_batches=self.prefetch_batches,
            drop_last=train,
            pad_final=not train,
            skip_corrupt=self.skip_corrupt_records,
        )

    def build_example_input(self) -> jax.Array:
        """A zero batch for Flax shape inference, derived from the first train
        record. Override for models whose input is not ``record['image']``."""
        record = self.train_dataset[0]
        image = record["image"]
        if self.train_dataloader.transform is not None:
            image = self.train_dataloader.transform(image, epoch=0, index=0)
        return jnp.zeros((1,) + tuple(np.shape(image)), jnp.float32)

    # ------------------------------------------------------------------
    # Train / validate loops
    # ------------------------------------------------------------------

    def train(self) -> None:
        """The epoch loop — structural twin of ``trainer/trainer.py:104-181``.
        One call is one ``trainer.train`` root span."""
        with annotate("trainer.train", epoch=self.cur_epoch):
            self._train()

    def _train(self) -> None:
        self._install_sigterm()
        self.metrics_writer.reopen()  # symmetric with the close() below
        assignment = None
        if self._streaming_train and hasattr(self.train_dataloader, "assignment"):
            # One record per attempt: after an elastic resume the loader's
            # extent was re-planned, so this IS the re-split assignment.
            assignment = dict(
                elastic=self._topology_changed,
                **self.train_dataloader.assignment(
                    cursor=self._resume_step_in_epoch
                    * self.train_dataloader.global_batch_size
                ),
            )
        self.run_telemetry.run_start(
            epoch=self.cur_epoch,
            max_epoch=self.max_epoch,
            step=self.state.step,
            resumed_step_in_epoch=self._resume_step_in_epoch,
            batch_size=self.batch_size,
            batch_replicas=self.batch_replicas,
            chain_steps=self.chain_steps,
            compute_dtype=str(jnp.dtype(self.precision.compute_dtype)),
            nonfinite_steps=self.nonfinite_steps,
            shard_assignment=assignment,
        )
        try:
            self._train_loop()
        finally:
            # Stop owning the process SIGTERM once training is over (or died):
            # a lingering handler would silently swallow later terminations.
            # The metrics writer closes here too, so that the preemption
            # early-return and the error paths flush it.
            self._restore_sigterm()
            # No background commit may be left in flight into interpreter
            # teardown, and a commit error here must not mask the original
            # exception (logged, not raised). close() also stops the commit
            # worker: a process constructing many Trainers must not collect
            # parked daemon threads (a re-entered train()'s next save restarts
            # it).
            self._flush_saver_logged()
            self.saver.close()
            self.run_telemetry.run_end(
                step=self.state.step,
                epoch=self.cur_epoch,
                preempted=self._preempted,
                nonfinite_steps=self.nonfinite_steps,
            )
            self.metrics_writer.close()

    def _train_loop(self) -> None:
        best_banner: dict | None = None
        for epoch in range(self.cur_epoch, self.max_epoch):
            self.cur_epoch = epoch

            with annotate("trainer.epoch", epoch=epoch):
                # Periodic validation + best-model tracking at the top of the
                # epoch (``:114-135`` — validates *before* this epoch's training;
                # best stores label `epoch`, deliberate parity with §2e).
                if self.have_validate and self.save_period and epoch % self.save_period == 0:
                    with annotate("trainer.validate", epoch=epoch):
                        metrics = self.validate()
                    if self._save_checkpoint(
                        BEST, epoch, reason="best", metrics=metrics, best=True
                    ):
                        best_banner = {"epoch": epoch, "metrics": dict(metrics)}
                    if best_banner is not None:
                        self.log(100 * "=")
                        msg = f"The BEST model is at EPOCH {best_banner['epoch']} and has "
                        for k, v in best_banner["metrics"].items():
                            msg += f" | {k.upper()} = {v} | "
                        self.log(msg)

                # Train one epoch (``:138-156``).
                self.train_dataloader.set_epoch(epoch)
                self.log(100 * "=")
                self.log(
                    f"[process {jax.process_index()}] Epoch {epoch + 1}/{self.max_epoch}"
                )
                epoch_metrics = self.train_epoch(epoch)

                with annotate("trainer.epoch_end", epoch=epoch):
                    # Preemption: save a resumable snapshot and stop. An interrupted
                    # epoch is labeled `epoch` (resume retrains it); a completed one
                    # `epoch + 1` — same labeling rule as the normal saves below.
                    # The decision is collective: a host whose signal arrived after
                    # the last in-epoch poll must not diverge from its peers here.
                    if self._collective_preempt_flag():
                        self._preempted = True
                        resume_epoch = epoch if self._epoch_interrupted else epoch + 1
                        # A mid-epoch interruption records its position so the resume
                        # skips the already-trained batches (the same stream continues);
                        # an epoch-boundary save restarts the next epoch at step 0.
                        loop_state = (
                            {"step_in_epoch": self._interrupted_at_step}
                            if self._epoch_interrupted
                            else None
                        )
                        self.events.emit(
                            "preemption",
                            epoch=epoch,
                            resume_epoch=resume_epoch,
                            step_in_epoch=self._interrupted_at_step
                            if self._epoch_interrupted
                            else 0,
                        )
                        self._save_checkpoint(
                            LAST, resume_epoch, loop_state=loop_state, wait=True,
                            reason="preemption",
                        )
                        self.log(
                            f"SIGTERM received — saved resumable snapshot (epoch "
                            f"{resume_epoch}"
                            + (
                                f", step {self._interrupted_at_step}"
                                if self._epoch_interrupted
                                else ""
                            )
                            + f") to {self.checkpoints.path(LAST)}; exiting",
                            "warning",
                        )
                        return

                    # Next-LR report (``:159-160``) — optax schedules are per-step.
                    next_lr = float(self.schedule(self.state.step))
                    self.log(f"THE NEXT LEARNING RATE VALUE IS {next_lr}")

                    # last / periodic checkpoint (``:163-172``): saved epoch is
                    # epoch+1 = the next epoch to train on resume (``:165-167``).
                    if self.have_validate:
                        if (epoch + 1) % self.last_save_period == 0 or epoch + 1 == self.max_epoch:
                            self._save_checkpoint(LAST, epoch + 1)
                            self.log(f"Saved model at epoch {epoch + 1}!")
                    elif self.save_period and epoch % self.save_period == 0:
                        self._save_checkpoint(epoch_checkpoint_name(epoch + 1), epoch + 1)
                        self.log(f"Saved model at epoch {epoch + 1}!")

                    # Epoch loss report — *global* means (pmean'd inside the step),
                    # upgrading the reference's local-only report (``:175-178``).
                    msg = "TOTAL GLOBAL TRAINING LOSS: "
                    for k, v in epoch_metrics.items():
                        msg += f" | {k} = {v} | "
                    self.log(msg)
                    self.metrics_writer.write(int(self.state.step), epoch_metrics, prefix="train")
                    self._write_precision_scalars()
                    self.run_telemetry.write_scalars(self.state.step)

        # Barrier: every queued background commit fully on disk (and any
        # commit error surfaced) before the run declares itself finished.
        # The wait IS checkpoint stall — the hot loop is over, but the run
        # cannot end until the commits land — so it books to `checkpoint`,
        # not `other`: a commit backlog (slow filesystem, commit_delay_s
        # chaos seam) must show up where the doctor's checkpoint-stall
        # verdict looks, not vanish into epoch glue.
        if self.goodput is not None:
            self.goodput.tick("other")
        with annotate("trainer.checkpoint", reason="flush"):
            self.saver.flush()
        if self.goodput is not None:
            self.goodput.tick("checkpoint")
        self.log("Finished!")

    def _log_sharded_layout(self) -> None:
        """One construction-time line saying what the mesh actually did to
        the state: how many leaves landed sharded, and the per-device vs
        global param bytes (the measurable ZeRO-3 win). Silent on a pure-DP
        mesh."""
        from distributed_training_pytorch_tpu.parallel import sharding as sharding_lib

        record = sharding_lib.sharding_record(self.state)
        if record is None:
            return
        n_sharded = len(record["specs"])
        # Denominator over the SAME tree the record scanned (the full
        # state): a sharded model_state leaf must not produce a >100%
        # fraction against a params+opt_state-only count.
        n_leaves = len(jax.tree.leaves(self.state))
        global_bytes = sharding_lib.tree_shard_bytes(
            self.state.params, jax.sharding.SingleDeviceSharding(jax.devices()[0])
        )
        per_device = sharding_lib.tree_shard_bytes(self.state.params)
        self.log(
            f"mesh {record['mesh']}: {n_sharded}/{n_leaves} state leaves "
            f"sharded; per-device param bytes {int(per_device)} "
            f"(global {int(global_bytes)})"
        )

    # ------------------------------------------------------------------
    # Elastic resume (docs/fault_tolerance.md "Elastic training")
    # ------------------------------------------------------------------

    def _peek_resume_checkpoint(self, snapshot_path, mesh, batch_size):
        """Resolve the resume checkpoint BEFORE the mesh is chosen.

        Returns the concrete checkpoint name/path to restore (or None for a
        cold start), maps ``"latest_valid"`` to the newest checkpoint passing
        integrity validation (the exact choice the restore will make —
        rejects emit ``checkpoint_rejected``), and reads its meta once (the
        restore site reuses it). When the recorded sharding topology
        disagrees with ``jax.device_count()``:

        * ``mesh=None`` — re-plan via :mod:`parallel.elastic`: the solved
          :class:`MeshConfig` replaces the default mesh and
          ``self.accum_steps`` is re-solved so the global batch math stays
          equivalent (``self._elastic_plan`` records the decision);
        * an explicit ``mesh`` — honored verbatim (the user already chose a
          current-backend layout); only the topology-change flag is set so
          the manager's :class:`TopologyMismatchError` seam stands down.

        Same-topology resumes and cold starts set nothing (host-side metadata
        reads only).
        """
        self._elastic_plan = None
        self._resume_meta = None
        self._resume_prevalidated = False
        self._topology_changed = False
        if snapshot_path is None:
            return None
        if snapshot_path == "latest_valid":
            if not self.checkpoints.checkpoint_names():
                # The automatic-restart entry point must be idempotent: on
                # the very first launch there is nothing to resume.
                self.log("no checkpoint to resume (latest_valid) — starting fresh")
                return None
            name = self.checkpoints.latest_valid_name()
            if name is None:
                # Same diagnostic the manager's restore_latest_valid raises:
                # name every checkpoint the walk rejected.
                raise CheckpointError(
                    f"no valid checkpoint under {self.checkpoints.directory} "
                    f"(invalid/corrupt: {self.checkpoints.checkpoint_names() or 'none found'})"
                )
            self._resume_prevalidated = True
            snapshot_path = name
        try:
            self._resume_meta = self.checkpoints.read_meta(snapshot_path)
        except Exception:  # noqa: BLE001 — the restore below raises the
            return snapshot_path  # canonical corrupt/missing error instead
        record = self._resume_meta.get("sharding")
        if not record:
            return snapshot_path  # pure-DP / pre-sharding: nothing to re-plan
        saved_axes = elastic_lib.record_axes(record)
        saved_devices = elastic_lib.axes_device_product(saved_axes)
        if saved_devices == jax.device_count():
            return snapshot_path
        self._topology_changed = True
        ckpt = os.path.basename(str(snapshot_path))
        if mesh is not None:
            self.log(
                f"resume checkpoint {ckpt!r} was written on {saved_devices} "
                f"devices (mesh {saved_axes}); this backend has "
                f"{jax.device_count()} — honoring the explicitly passed mesh "
                "(no re-plan; accumulation unchanged)."
            )
            return snapshot_path
        self._elastic_plan = elastic_lib.replan(
            saved_axes,
            jax.device_count(),
            batch_size=batch_size,
            accum_steps=self.accum_steps,
        )
        self.accum_steps = self._elastic_plan.accum_steps
        self.log(
            f"elastic restore: checkpoint {ckpt!r} was written on "
            f"{saved_devices} devices (mesh {saved_axes}); re-planned for "
            f"{jax.device_count()} devices as mesh "
            f"{self._elastic_plan.new_axes} with accum_steps="
            f"{self.accum_steps} (was {self._elastic_plan.old_accum_steps}) "
            "— same effective global batch."
        )
        return snapshot_path

    def _emit_elastic_restore(self, snapshot_path) -> None:
        """One ``elastic_restore`` flight record per topology-changed resume
        (docs/observability.md): old/new mesh axes and device counts, the
        old/new accumulation factors, and the re-plan reason."""
        if not self._topology_changed:
            return
        plan = self._elastic_plan
        if plan is not None:
            fields = plan.event_fields()
        else:
            record = (self._resume_meta or {}).get("sharding") or {}
            old_axes = elastic_lib.record_axes(record)
            fields = {
                "from_mesh": old_axes,
                "to_mesh": {str(k): int(v) for k, v in self.mesh.shape.items()},
                "from_devices": elastic_lib.axes_device_product(old_axes),
                "to_devices": jax.device_count(),
                "old_accum_steps": self.accum_steps,
                "accum_steps": self.accum_steps,
                "reason": "explicit mesh (no re-plan)",
            }
        self.events.emit(
            "elastic_restore",
            name=os.path.basename(str(snapshot_path)),
            replanned=plan is not None,
            **fields,
        )

    @property
    def exporter(self):
        """The status exporter while ``train()`` runs with
        ``Telemetry(export_port=...)``, else None."""
        return self.run_telemetry.exporter

    @property
    def _flops_per_step(self):
        return self.run_telemetry.flops_per_step

    @property
    def _peak_flops(self):
        return self.run_telemetry.peak_flops

    @property
    def model_dtype(self):
        """The activation dtype matching this trainer's precision policy —
        pass as ``dtype=`` when constructing models in ``build_model`` so
        model-internal casts agree with the policy's boundary casts
        (``jnp.float32`` under the default fp32 policy: identical models)."""
        return self.precision.compute_dtype

    def _write_precision_scalars(self) -> None:
        """TensorBoard observability for dynamic loss scaling: the current
        scale and the cumulative overflow-skip count, next to the train
        scalars. No-op (like every MetricsWriter call) without tensorboardX
        or off process 0; no-op entirely unless a DynamicScale is active."""
        scale_state = getattr(self.state, "loss_scale", None)
        if not is_dynamic(scale_state):
            return
        self.metrics_writer.write(
            int(self.state.step),
            {
                "loss_scale": float(scale_state.scale),
                "skipped_steps": float(scale_state.skipped_steps),
            },
            prefix="precision",
        )

    def _flush_saver_logged(self) -> None:
        """Flush the async saver, reporting — never raising — a background
        commit failure. For the paths where an exception would defeat the
        path's own purpose: teardown (masking the original error), the
        emergency-save exit (aborting the grace-window shutdown), and the
        nan rollback (dying instead of degrading)."""
        err = self.saver.flush(raise_errors=False)
        if err is not None:
            self.log(f"background checkpoint commit failed: {err}", "error")

    def _on_async_commit(self, name: str, seconds: float) -> None:
        """Background-commit completion callback (runs on the saver's worker
        thread): book the commit's wall time to the ``checkpoint_async``
        goodput bucket — time the hot loop did NOT stall for — and leave a
        ``checkpoint_commit`` record in the flight log. Both sinks are
        thread-safe (``GoodputMeter.account`` touches a bucket the tick
        stream never writes; ``EventLog.emit`` locks)."""
        if self.goodput is not None:
            self.goodput.account("checkpoint_async", seconds)
        self.events.emit("checkpoint_commit", name=name, commit_ms=seconds * 1e3)

    def _save_checkpoint(
        self,
        name: str,
        epoch: int,
        *,
        loop_state: Mapping | None = None,
        wait: bool = False,
        reason: str = "epoch",
        metrics: Mapping | None = None,
        best: bool = False,
    ) -> bool:
        """Checkpoint save + telemetry, one implementation for every trainer
        save site (last / periodic / preemption / best).

        Two modes (docs/fault_tolerance.md state machine):

        * **async** (``async_checkpoint=True`` and ``wait=False`` — the
          periodic/best saves): device->host snapshot on this thread, commit
          on the saver's background thread. Only the snapshot stall lands in
          the ``checkpoint`` goodput bucket; the background commit books
          itself to ``checkpoint_async`` via ``_on_async_commit``.
        * **emergency** (``wait=True`` — preemption and watchdog saves, or
          ``async_checkpoint=False``): flush any in-flight background save
          (completing it, never abandoning it), then commit synchronously —
          the save must be durable inside the eviction grace window. The
          full wall time is hot-loop stall, booked to ``checkpoint``.

        ``best=True`` routes through the manager's best-fitness rule;
        returns whether a checkpoint was written."""
        if self.goodput is not None:
            self.goodput.tick("other")  # close the epoch-glue interval
        with annotate("trainer.checkpoint", epoch=epoch, reason=reason):
            mode = "async" if (self._async_saves and not wait) else "sync"
            telemetry_meta = self.run_telemetry.checkpoint_meta()
            # Streaming reader state rides EVERY save (sync/async/emergency/best
            # — this is the one save site): epoch is the resume epoch the caller
            # passed, cursor the global records already consumed in it (0 for an
            # end-of-epoch save; step_in_epoch * G for a preemption save).
            data_state = None
            if self._streaming_train:
                data_state = self.train_dataloader.reader_state(
                    epoch=epoch,
                    batches_consumed=int((loop_state or {}).get("step_in_epoch", 0)),
                )
            snapshot_s = None
            save_s = None  # full synchronous-save stall (the sync-mode twin of
            #                snapshot_s) — the timeline's `save:` span duration
            if best:
                if mode == "async":
                    saved, snapshot_s = self.saver.maybe_save_best(
                        metrics, self.state, epoch, telemetry=telemetry_meta,
                        data_state=data_state,
                    )
                else:
                    t_save = time.perf_counter()
                    saved = self.checkpoints.maybe_save_best(
                        metrics, self.state, epoch, telemetry=telemetry_meta,
                        data_state=data_state,
                    )
                    save_s = time.perf_counter() - t_save
            else:
                if mode == "async":
                    snapshot_s = self.saver.save_async(
                        name, self.state, epoch, metrics=metrics,
                        loop_state=loop_state, telemetry=telemetry_meta,
                        data_state=data_state,
                    )
                else:
                    save_s = self.saver.save_sync(
                        name, self.state, epoch, metrics=metrics,
                        loop_state=loop_state, telemetry=telemetry_meta,
                        data_state=data_state,
                    )
                saved = True
            if wait:
                # The emergency save above is already durable; a PRIOR background
                # commit's failure (re-stashed by save_sync) must be reported,
                # not abort the grace-window exit this save exists to protect.
                self._flush_saver_logged()
        if self.goodput is not None:
            self.goodput.tick("checkpoint" if saved else "other")
        if saved:
            fields = {"name": name, "epoch": epoch, "reason": reason, "mode": mode}
            if snapshot_s is not None:
                fields["snapshot_ms"] = snapshot_s * 1e3
            elif save_s is not None:
                fields["save_ms"] = save_s * 1e3
            if loop_state:
                fields["step_in_epoch"] = int(loop_state.get("step_in_epoch", 0))
            self.events.emit("checkpoint_save", **fields)
            if data_state is not None:
                # The data plane's save record: which records a
                # resume from this checkpoint will consume next.
                self.events.emit(
                    "data_reader_state",
                    name=name,
                    reason=reason,
                    epoch=int(data_state["epoch"]),
                    cursor=int(data_state["cursor"]),
                    seed=int(data_state["seed"]),
                    record_count=int(data_state["record_count"]),
                    assignment_version=int(data_state["assignment_version"]),
                )
        return saved

    def _run_memory_preflight(self, *, can_chain: bool) -> None:
        """One-shot OOM preflight on the first unit's abstract shapes
        (``memory.preflight.run_preflight``): predicted peak vs per-device
        capacity, a ``memory_preflight`` event, and on predicted OOM a
        fail-fast :class:`~memory.PreflightOOMError` carrying the max-batch /
        microbatch recommendations. ``can_chain`` gates the chained-window
        prediction (the caller knows whether a full window can still occur
        this epoch — conservative at window granularity: lead-single
        realignment may rarely leave the last possible window unformed, in
        which case the verdict covers a slightly larger program than
        dispatches). Skipped (with a warning) under a custom ``train_step``
        override: the engine's program is then not the one dispatched."""
        self._preflight_done = True
        if type(self).train_step is not Trainer.train_step:
            self.log(
                "memory preflight skipped: custom train_step override — the "
                "engine program the preflight would lower is not the one "
                "this trainer dispatches",
                "warning",
            )
            return
        self.memory_report = run_preflight(
            self.engine,
            self.state,
            self._abstract_batch,
            self.preflight,
            chain_length=self.chain_steps if can_chain else None,
            log=self.log,
            events=self.events,
        )

    def _profile_flops_index(self):
        """Per-op roofline join table for the profile capture's top-op rows
        (``profiling.report.flops_index`` over the engine's observability
        probe — same one-time off-hot-path compile discipline as the MFU
        probe: dispatch executables and ``trace_counts`` untouched). Returns
        None (rows carry no FLOPs/bytes) before the first batch's shapes are
        known or when the probe's module is not the program that was traced:
        a custom ``train_step`` override, or ``chain_steps > 1`` — the trace
        then covers the chained-scan executable, whose per-module instruction
        numbering does not line up with the single-step probe's, and a
        name-keyed join would attach a DIFFERENT instruction's flops/bytes to
        a colliding low-numbered name (confidently wrong roofline columns are
        worse than none)."""
        if (
            self._abstract_batch is None
            or self.chain_steps > 1
            or type(self).train_step is not Trainer.train_step
        ):
            return None
        from distributed_training_pytorch_tpu.profiling.report import flops_index

        return flops_index(
            self.engine.compile_step_probe(self.state, self._abstract_batch)
        )

    def _validate_chain_config(self) -> None:
        """Reject/round knob combinations that would silently misalign with
        chained-window execution — fail loudly at construction, not as a
        drifted log cadence or a preemption poll that never fires."""
        if self.chain_steps < 1:
            raise ValueError(f"chain_steps must be >= 1, got {self.chain_steps}")
        if self.chain_steps == 1:
            return
        if type(self).train_step is not Trainer.train_step:
            raise ValueError(
                "chain_steps > 1 requires the engine-backed default train_step: "
                f"{type(self).__name__} overrides train_step, which executes "
                "per-step Python the chained device program cannot call. Keep "
                "chain_steps=1, or move the customization into build_loss_fn "
                "(traced into the compiled step, chains fine)."
            )
        if self.log_every and self.log_every % self.chain_steps:
            raise ValueError(
                f"log_every ({self.log_every}) must be a multiple of "
                f"chain_steps ({self.chain_steps}): intra-epoch loss syncs "
                "happen at window boundaries, so a non-multiple would silently "
                "drift the log cadence. Round log_every or chain_steps."
            )
        if self.preemption_check_every and self.preemption_check_every % self.chain_steps:
            rounded = (
                -(-self.preemption_check_every // self.chain_steps) * self.chain_steps
            )
            self.log(
                f"preemption_check_every={self.preemption_check_every} is not a "
                f"multiple of chain_steps={self.chain_steps} — rounded up to "
                f"{rounded} so multi-host preemption votes land on window "
                "boundaries (they cannot fire mid-window).",
                "warning",
            )
            self.preemption_check_every = rounded
        if self.step_timeout:
            self.log(
                f"chain_steps={self.chain_steps}: the hung-step watchdog pats "
                f"once per window, so its effective timeout scales to "
                f"step_timeout x chain_steps = {self.step_timeout * self.chain_steps}s."
            )

    def _trace_total(self) -> int:
        return sum(self.engine.trace_counts.values())

    def _fault_active_in_window(self, epoch: int, start: int, stop: int) -> bool:
        return self.fault_plan is not None and self.fault_plan.active_in_window(
            epoch, start, stop
        )

    def _pat_watchdog(self, watchdog, timeout):
        """Arm (first completed step only — the first step includes XLA
        compilation, minutes for a real model: arming before it would SIGTERM
        mid-compile and the resumed run would recompile and die the same way,
        a restart livelock) and pat the hung-step watchdog."""
        if not timeout:
            return watchdog
        if watchdog is None:
            # max_fires=2: fire 1 = graceful SIGTERM save; fire 2 = the
            # thread is wedged, hard-exit (_on_hung_step).
            watchdog = StepWatchdog(
                timeout,
                self._on_hung_step,
                max_fires=2,
                on_patrol=self.run_telemetry.patrol_hook,
            ).start()
        watchdog.pat()
        return watchdog

    def train_epoch(self, epoch: int) -> dict:
        """One epoch of the step loop: fetch a unit from the input path
        (``data.epoch_units``), dispatch it, repeat. A unit is one compiled
        step on one global batch or, with ``chain_steps > 1``, ONE compiled
        program over a window of ``chain_steps`` batches
        (``engine.train_steps_chained``). Metrics stay on the device either
        way (a window's come back stacked, one row a step) and reach the host
        at the ``log_every`` syncs and in one ``device_get`` at the end.

        Mid-epoch resume: an epoch interrupted by a preemption save at step k
        skips its first k batches (the loader's permutation and the
        per-(epoch, index) augmentation keys are deterministic, so the rest of
        the stream is the one the interrupted run would have seen) and, under
        chaining, runs single steps up to the next window boundary, so that a
        resumed run executes the same windows as an uninterrupted one."""
        tel = self.run_telemetry
        # `unit` ids of the spans: the global step of a unit's first step,
        # reckoned on the host (reading state.step would be a device sync).
        num_batches = len(self.train_dataloader)
        first_unit = epoch * num_batches
        with annotate("trainer.epoch_start", epoch=epoch):
            # (k, tree) records: k == 1 one step's scalar metrics, k > 1 a
            # window's stacked outputs, kept UNsliced: slicing per step here
            # would issue k x num_keys tiny device ops after every dispatch.
            collected: list[tuple[int, Any]] = []
            skip_steps = self._resume_step_in_epoch
            self._resume_step_in_epoch = 0  # consumed by the first trained epoch
            step_in_epoch = skip_steps
            executed = 0
            synced_entries = 0  # index into `collected` of the last nan-policy sync
            synced_steps = 0  # the same sync position, in steps
            t0 = time.perf_counter()
            tel.epoch_start(resumed=skip_steps > 0, traces=self._trace_total())
            chain = self.chain_steps
            units = epoch_units(
                self.train_dataloader, self.mesh, chain_steps=chain, skip_steps=skip_steps,
                preprocess=lambda b: self._check_image_range(self.preprocess_batch(b)),
                epoch=epoch, first_unit=first_unit,
            )
            bar = self._progress_bar(num_batches, f"epoch {epoch + 1}")
            self._epoch_interrupted = False
            # Capture transitions fire at unit boundaries: a window is traced whole.
            cap = self._profile_capture
            watchdog = None
            # The watchdog is patted once a unit; a window takes ~`chain` step times.
            watchdog_timeout = self.step_timeout * chain if self.step_timeout else None
            self._watchdog_timeout = watchdog_timeout

        def sync_log_point():
            # The intra-epoch host syncs are this one (always at a unit
            # boundary: log_every % chain_steps == 0 is ctor-enforced) and,
            # multi-host only, the preemption vote.
            nonlocal synced_entries, synced_steps
            n_last, last = collected[-1]
            arrivals = tel.sample_arrivals(
                last, fault_plan=self.fault_plan, epoch=epoch, step_in_epoch=step_in_epoch
            )
            m = {k: float(v[-1]) if n_last > 1 else float(v) for k, v in last.items()}
            m_check = m
            if "nonfinite" in m:
                # A guarded poison at an earlier step has nonfinite=1 only in
                # ITS metrics, so the policy sees every step since the last
                # sync (windows report the flag per step).
                m_check = dict(m)
                m_check["nonfinite"] = float(
                    sum(np.sum(np.asarray(x["nonfinite"])) for _, x in collected[synced_entries:])
                )
                synced_entries = len(collected)
                synced_steps = executed
            self._apply_nan_policy(m_check)
            rate = executed * self.batch_size / (time.perf_counter() - t0)
            if bar is not None:
                bar.set_postfix(m, refresh=False)
                bar.clear()  # keep log lines off the live bar row
            self.log(f"  step {step_in_epoch}/{num_batches} {m} ({rate:.1f} img/s)")
            if bar is not None:
                bar.refresh()
            tel.log_sync(
                m, arrivals, epoch=epoch, step_in_epoch=step_in_epoch,
                executed=executed, traces=self._trace_total(),
            )

        def run_unit(step_fn, batch, n) -> bool:
            """The one dispatch sequence: ``n`` steps in one call into the
            engine. False when a preemption was agreed on instead; it is
            polled at unit boundaries only (a device program has no mid-window
            host hook), so saves land on them."""
            nonlocal step_in_epoch, executed, watchdog
            if self._preemption_requested(step_in_epoch):
                self._preempted = True  # collective (multi-host OR)
                return False
            if cap is not None:
                cap.maybe_start(step_in_epoch, self.state.params)
            with annotate(
                "engine.dispatch", epoch=epoch, unit=first_unit + step_in_epoch, steps=n
            ) as span:
                # `traced`: the call traced and compiled (or loaded from the
                # compile cache) a program; jit does that inside the call.
                before = self._trace_total()
                args = (self.state, batch, n) if n > 1 else (self.state, batch)
                self.state, metrics = step_fn(*args)
                span.set(traced=self._trace_total() > before)
            collected.append((n, metrics))
            step_in_epoch += n
            executed += n
            if cap is not None:
                cap.maybe_stop(step_in_epoch, self.state.params)
            watchdog = self._pat_watchdog(watchdog, watchdog_timeout)
            if bar is not None:
                # Host-only; the postfix refreshes at the log_every syncs (a
                # live per-step loss would be a device sync every step).
                bar.update(n)
            if self.log_every and step_in_epoch % self.log_every == 0:
                with annotate("trainer.sync", epoch=epoch, reason="log_every"):
                    sync_log_point()
            return True

        try:
            interrupted = False
            while not interrupted:
                with annotate("trainer.fetch", epoch=epoch, unit=first_unit + step_in_epoch):
                    unit = next(units, None)  # the epoch's last finds the ring finished
                tel.fetched()
                if unit is None:
                    break
                n, batch = unit
                if self._abstract_batch is None:
                    # Shapes only, no device ops; a window leaf [n, B, ...]
                    # loses its leading step axis.
                    self._abstract_batch = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape if n == 1 else x.shape[1:], x.dtype),
                        batch,
                    )
                if self.preflight is not None and not self._preflight_done:
                    # Before anything compiles. The verdict covers the chained
                    # window only when one can still occur this epoch: an
                    # epoch shorter than a window dispatches singles only, and
                    # a verdict on a program that never runs could fail a run
                    # whose real program fits.
                    self._run_memory_preflight(
                        can_chain=chain > 1 and num_batches - step_in_epoch >= chain
                    )
                    tel.preflight_ran()
                unit_traces = self._trace_total()
                if n > 1 and not self._fault_active_in_window(
                    epoch, step_in_epoch, step_in_epoch + n
                ):
                    interrupted = not run_unit(self.engine.train_steps_chained, batch, n)
                else:
                    # Lead and tail units, chain_steps == 1, and windows with a
                    # fault pending: those are unstacked so that the per-step
                    # injection points and preemption polls run.
                    singles = (
                        (batch,)
                        if n == 1
                        else (self.engine.unstack_window(batch, i) for i in range(n))
                    )
                    for b in singles:
                        if self.fault_plan is not None:
                            b = self._inject_step_faults(b, epoch, step_in_epoch)
                        if not run_unit(self.train_step, b, 1):
                            interrupted = True
                            break
                tel.unit_done(
                    self._trace_total() - unit_traces, epoch=epoch, step_in_epoch=step_in_epoch
                )
            if interrupted:
                self._epoch_interrupted = True
                self._interrupted_at_step = step_in_epoch
        except BaseException:
            # An abort with a capture window open must still stop the
            # PROCESS-GLOBAL jax.profiler session, or every later start_trace
            # in this process fails. sync=None: never block teardown on
            # (possibly hung) device work; abort=True: no trace analysis or
            # roofline compile ahead of the emergency-save path.
            if cap is not None and cap.state == "tracing":
                cap.maybe_stop(step_in_epoch, None, force=True, abort=True)
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
        if cap is not None:
            # A window still open (short epoch). An interrupted epoch is on
            # the emergency-save clock: abort, as above.
            cap.maybe_stop(
                step_in_epoch, self.state.params, force=True, abort=self._epoch_interrupted
            )
        if bar is not None:
            bar.close()
        if not collected:
            return {}
        # ONE host transfer for the whole epoch; window records are expanded
        # to per-step dicts on the host (numpy indexing, no device ops).
        host: list[dict] = []
        with annotate("trainer.sync", epoch=epoch, reason="epoch_drain"):
            drained = jax.device_get(collected)
        for k, tree in drained:
            if k == 1:
                host.append(tree)
            else:
                host.extend({key: v[i] for key, v in tree.items()} for i in range(k))
        tel.drained()
        with annotate("trainer.epoch_end", epoch=epoch):
            # The wall is closed BEFORE the one-time FLOP probe: its compile
            # would inflate this epoch's step time into a spurious
            # step_time_regression.
            epoch_wall = time.perf_counter() - t0
            tel.probe_flops(
                self.engine, self.state, self._abstract_batch,
                engine_step_runs=type(self).train_step is Trainer.train_step,
            )
            out = self._aggregate_epoch_metrics(host, synced_steps)
            tel.epoch_end(
                out, epoch=epoch, step_in_epoch=step_in_epoch, executed=executed,
                wall_s=epoch_wall, interrupted=self._epoch_interrupted,
                traces=self._trace_total(), nonfinite_steps=self.nonfinite_steps,
            )
        return out

    def _aggregate_epoch_metrics(self, host: list[dict], synced: int = 0) -> dict:
        """Per-epoch means. Under the non-finite guard, poisoned steps are
        excluded from the means (their loss is NaN by construction — averaging
        it in would report a NaN epoch even though training recovered) and
        ``nonfinite`` reports the skipped-step COUNT instead. The policy check
        covers only steps after the last intra-epoch sync (``synced``) — a
        poison already handled at a log_every sync must not re-trigger."""
        if "nonfinite" not in host[0]:
            out = {k: float(np.mean([m[k] for m in host])) for k in host[0]}
            self._apply_nan_policy(out)
            return out
        bad = int(np.sum([m["nonfinite"] for m in host]))
        self.nonfinite_steps += bad
        good = [m for m in host if not m["nonfinite"]]
        out = {
            k: float(np.mean([m[k] for m in good])) if good else float("nan")
            for k in host[0]
            if k != "nonfinite"
        }
        out["nonfinite"] = float(bad)
        check = dict(out)
        check["nonfinite"] = float(np.sum([m["nonfinite"] for m in host[synced:]]))
        self._apply_nan_policy(check)
        return out

    def _apply_nan_policy(self, host_metrics: dict) -> None:
        """Run at host sync points only (log_every / epoch end) — detection
        adds zero extra device syncs. ``host_metrics`` values are floats."""
        if self.nan_policy is None:
            return
        poisoned = host_metrics.get("nonfinite", 0.0) > 0 or any(
            not np.isfinite(v) for v in host_metrics.values()
        )
        if not poisoned:
            return
        if self.nan_policy == "raise":
            raise NonFiniteLossError(
                f"non-finite training metrics: {host_metrics} "
                "(nan_policy='raise'; use 'skip' or 'restore_last_good' to "
                "degrade gracefully)"
            )
        if self.nan_policy == "restore_last_good":
            # Serialize with the background committer: the rollback must see
            # a fully committed newest checkpoint (and the manager is
            # single-threaded by contract — see AsyncCheckpointSaver).
            self._flush_saver_logged()
            try:
                self.state, epoch, name = self.checkpoints.restore_latest_valid(
                    self.state
                )
            except CheckpointError:
                # Nothing saved yet (NaN before the first checkpoint): the
                # engine guard already dropped the poisoned update, so
                # degrading to skip-semantics is safe — and still graceful.
                self.log(
                    "non-finite step detected but no valid checkpoint exists "
                    "yet — update was skipped, training continues",
                    "warning",
                )
                return
            self.nonfinite_rollbacks += 1
            self.log(
                f"non-finite step detected — rolled state back to checkpoint "
                f"{name!r} (epoch {epoch})",
                "warning",
            )

    def _inject_step_faults(self, batch, epoch: int, step: int):
        """Deterministic fault-injection points (fault/inject.py): a real
        SIGTERM, a simulated hung step, or a NaN-poisoned batch. Every
        firing lands in the telemetry event log (rank-0, no-op when off) so
        a test run's flight record shows exactly which faults fired where."""
        fired_before = len(self.fault_plan.fired)
        self.fault_plan.maybe_sigterm(epoch=epoch, step=step)
        hang = self.fault_plan.fires("hang", epoch=epoch, step=step)
        if hang is not None:
            time.sleep(float(hang.payload or 0.0))
        if self.fault_plan.fires("nan_loss", epoch=epoch, step=step) is not None:
            batch = jax.tree.map(
                lambda x: jnp.full_like(x, jnp.nan)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                batch,
            )
        if self.events.enabled:
            for kind, ctx in self.fault_plan.fired[fired_before:]:
                self.events.emit("fault_injection", kind=kind, **ctx)
        return batch

    _hung_once = False

    def _on_hung_step(self) -> None:
        # Watchdog-thread callback. First fire: reuse the preemption
        # machinery (SIGTERM -> flag -> collective save at the next safe
        # point) — recovers steps that are slow but eventually return.
        # Second fire: the main thread is truly wedged (blocked inside a
        # collective or I/O call that will never return to the loop's
        # preemption check), so a graceful save is impossible — hard-exit
        # with EX_TEMPFAIL so the scheduler restarts from the last
        # checkpoint. That IS the bounded loss; the alternative is a silent
        # stall until the job-level timeout.
        timeout = self._watchdog_timeout or self.step_timeout
        if self._hung_once:
            self.log(
                f"watchdog: still no progress {timeout}s after "
                "SIGTERM — main thread is wedged; hard-exiting for scheduler "
                "restart (resume from the last checkpoint)",
                "error",
            )
            os._exit(75)  # EX_TEMPFAIL
        self._hung_once = True
        self.log(
            f"watchdog: no step completed in {timeout}s — forcing a "
            "preemption-style resumable save",
            "warning",
        )
        self.run_telemetry.hung_step(timeout)
        os.kill(os.getpid(), signal.SIGTERM)

    def _on_preemption_signal(self, signum, frame) -> None:
        # Flag only — saves are collective and cannot run in signal context.
        self._preempted = True
        # Chain to whatever handler was installed before this trainer, so a
        # Trainer never swallows someone else's SIGTERM semantics.
        if callable(self._prev_sigterm):
            self._prev_sigterm(signum, frame)

    def _install_sigterm(self) -> None:
        if not self.save_on_preemption or self._sigterm_installed:
            return
        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM, self._on_preemption_signal)
            self._sigterm_installed = True
        except ValueError:
            pass  # not the main thread (e.g. trainer driven from a worker)

    def _restore_sigterm(self) -> None:
        if self._sigterm_installed:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm or signal.SIG_DFL)
            except ValueError:
                pass
            self._sigterm_installed = False

    def _preemption_requested(self, step_in_epoch: int) -> bool:
        """Collective preemption decision. Per-host SIGTERM delivery is not
        synchronized; if each host acted on its local flag alone, hosts could
        break on different steps — one skipping a collective its peers entered
        (deadlock inside the eviction grace window). All hosts therefore agree
        on the OR of their flags at the same loop points, every
        ``preemption_check_every`` steps — a bounded reaction latency
        independent of ``log_every`` (an ImageNet epoch is far longer than an
        eviction grace window, so epoch-boundary-only checking is not enough).
        Single-process polls its local flag every step for free."""
        if jax.process_count() == 1:
            return self._preempted
        cadence = self.preemption_check_every
        if not cadence or step_in_epoch % cadence != 0:
            return False
        return self._collective_preempt_flag()

    def _collective_preempt_flag(self) -> bool:
        """OR of every host's local flag — identical answer on all hosts.
        Must be called at the same program points on every host."""
        if jax.process_count() == 1:
            return self._preempted
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([self._preempted], dtype=np.bool_)
        )
        return bool(np.any(flags))

    def _progress_bar(self, total: int, desc: str):
        """Live per-step progress display (reference shows a tqdm bar with live
        postfix metrics, ``trainer/trainer.py:143,148``). Process 0 only."""
        if not self.progress or jax.process_index() != 0:
            return None
        try:
            from tqdm import tqdm
        except ImportError:
            return None
        return tqdm(total=total, desc=desc, dynamic_ncols=True, leave=False)

    def validate(self) -> dict:
        """Collective validation over the val loader; returns weighted-mean
        metrics (pad-mask aware). Twin of ``trainer/trainer.py:184-206``."""
        sums: dict[str, Any] = {}
        weight_total = 0.0
        mask_contract_checked = False
        for b, host_batch in enumerate(self.val_dataloader):
            host_batch = self.preprocess_batch(host_batch)
            # Weight by the batch's GLOBAL real-row count — identical on every
            # process (a host-local mask sum would diverge across hosts on the
            # padded final batch and break collective best-checkpoint decisions).
            if hasattr(self.val_dataloader, "global_real_count"):
                weight = float(self.val_dataloader.global_real_count(b))
            else:
                weight = float(len(next(iter(host_batch.values()))))
            # Contract check (once, on the first batch that actually contains
            # padding — global real count below the global batch size):
            # real-count weighting is only exact when the user's metrics
            # down-weight padded rows via batch["mask"] (ops.weighted_mean).
            # A criterion that ignores the mask gets pad-diluted values
            # silently combined with real-row weights.
            if (
                not mask_contract_checked
                and "mask" in host_batch
                and np.asarray(host_batch["mask"]).min() == 0  # padding present
            ):
                mask_contract_checked = True
                if getattr(self, "criterion_uses_mask", None) is not True:
                    self.log(
                        "this validation batch is padded (batch['mask']): "
                        "metrics must down-weight padded rows (ops.weighted_mean) "
                        "or they are diluted. Set self.criterion_uses_mask = True "
                        "once your build_criterion handles the mask to silence "
                        "this.",
                        "warning",
                    )
            batch = self.engine.shard_batch(host_batch)
            metrics = self.validate_step(self.state, batch)
            # Weighted sums accumulate as device scalars; the epoch's single
            # host sync is the device_get below (the reference syncs per batch
            # via .item(), ``example_trainer.py:101-102``).
            for k, v in dict(metrics).items():
                sums[k] = sums.get(k, 0.0) + v * weight
            weight_total += weight
        sums = jax.device_get(sums)
        avg = {k: float(v) / max(weight_total, 1.0) for k, v in sums.items()}
        msg = "VALIDATE RESULTS: "
        for k, v in avg.items():
            msg += f" | {k} = {v} | "
        self.log(msg)
        self.metrics_writer.write(int(self.state.step), avg, prefix="val")
        return avg

    # ------------------------------------------------------------------
    # The nine hooks (``trainer/trainer.py:219-253``) — same names.
    # ------------------------------------------------------------------

    def build_train_dataset(self):
        raise NotImplementedError("Please implement the build_train_dataset method")

    def build_val_dataset(self):
        raise NotImplementedError("Please implement the build_val_dataset method")

    def build_model(self):
        raise NotImplementedError("Please implement the build_model method")

    def build_criterion(self):
        raise NotImplementedError("Please implement the build_criterion method")

    def build_optimizer(self, schedule: optax.Schedule):
        raise NotImplementedError("Please implement the build_optimizer method")

    def build_scheduler(self):
        raise NotImplementedError("Please implement the build_scheduler method")

    def build_sharding_rules(self):
        """Advanced hook (the ``build_loss_fn`` convention): the explicit
        ``(path_regex, PartitionSpec)`` parameter-sharding rules handed to
        the engine when the ctor's ``sharding_rules="auto"`` (the default).
        The default is ``parallel.default_sharding_rules(mesh)`` — the one
        resolution policy shared with bench.py's BENCH_MESH setup, so the
        bench measures the same program the Trainer runs: a mesh with a
        nontrivial ``tensor`` axis gets ``transformer_tp_rules()``
        (Megatron-style TP for the ViT/LM transformer blocks — conv models
        match none of its patterns and fall through to the FSDP/replicated
        fallback), any other mesh gets None (pure FSDP via ``spec_for_leaf``
        / ``_fsdp_spec``, or fully replicated on a pure-data mesh). Override
        to hand-place specs for a custom model."""
        from distributed_training_pytorch_tpu.parallel import (
            default_sharding_rules,
        )

        return default_sharding_rules(self.mesh)

    def build_loss_fn(self):
        """Advanced hook (beyond the reference's nine): the full functional
        LossFn handed to the engine. The default composes ``build_model`` +
        ``build_criterion`` the standard way; override when the loss needs
        direct access to params (e.g. ``ops.losses.tied_cross_entropy_loss`` fusing
        a tied LM head so the [B, T, V] logits never materialize)."""
        return make_supervised_loss(self.model, self.criterion)

    def preprocess_batch(self, batch: Mapping) -> Mapping:
        """Host-side batch hook. The reference uses this for the H2D copy
        (``example_trainer.py:68-70``); here transfer is the framework's job,
        so the default is identity."""
        return batch

    _image_range_checked = False

    def _check_image_range(self, batch: Mapping) -> Mapping:
        """One-time foot-gun guard (the whole first train batch, once a
        trainer): a FLOAT image batch whose values span raw-pixel range almost
        certainly missed its normalize — ``models.InputNormalizer`` passes
        floats through as already normalized, so the model would train on
        ~100x-misscaled input with no error anywhere else."""
        if not self._image_range_checked:
            self._image_range_checked = True
            img = batch.get("image") if hasattr(batch, "get") else None
            if img is not None and np.issubdtype(np.asarray(img).dtype, np.floating):
                hi = float(np.max(np.abs(np.asarray(img))))
                if hi > 16.0:  # normalized images sit within a few sigma of 0
                    self.log(
                        f"float image batch spans |x| up to {hi:.0f} — looks like "
                        "raw 0-255 pixels. Float inputs bypass on-device "
                        "normalization (InputNormalizer passes them through); "
                        "ship uint8 or normalize on host.",
                        "warning",
                    )
        return batch

    def train_step(self, state, batch):
        """Default: the engine's compiled grad/reduce/update step."""
        return self.engine.train_step(state, batch)

    def validate_step(self, state, batch):
        """Default: the engine's compiled collective eval step."""
        return self.engine.eval_step(state, batch)

    # ------------------------------------------------------------------
    # Lifecycle statics — ``ddp_setup``/``destroy_process`` twins (``:74-82``).
    # ------------------------------------------------------------------

    @staticmethod
    def distributed_setup(**kwargs) -> None:
        mesh_lib.setup_distributed(**kwargs)

    @staticmethod
    def destroy_process() -> None:
        mesh_lib.shutdown_distributed()
