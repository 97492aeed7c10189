"""Named-checkpoint store with best / last / periodic policies + resume.

Capability twin of the reference snapshot subsystem
(``trainer/trainer.py:85-101`` ``_save_snapshot``/``_load_snapshot`` and the
policy logic at ``:114-135,163-172``):

* three named policies — ``best`` (on validation-metric improvement per a
  ``(metric, "geq"|"leq")`` rule, ``trainer/trainer.py:118-124``), ``last``
  (every validating epoch, ``:164-165``) and ``checkpoint_epoch_N`` (every
  ``save_period`` epochs otherwise, ``:166-167``);
* the snapshot payload {epoch, model, optimizer, scheduler state}
  (``:85-92``) becomes {TrainState pytree, meta json} — optax schedules are
  pure functions of ``state.step`` so there is no separate scheduler state;
* resume restores ``cur_epoch`` so the epoch loop continues mid-schedule
  (``:96-101``, ``:110``).

Crash consistency (the fault-tolerance upgrade over both the reference and
the plain Orbax layout):

* **Atomic commits** — every save lands in ``directory/.staging/<name>.<n>``
  first; only after the write fully completes (async saves included) is the
  staging dir renamed onto ``directory/<name>``. A reader can never observe
  a partially-written checkpoint under a final name, no matter where the
  process dies. Crash leftovers (orphaned staging dirs, a half-finished
  swap) are repaired on the next manager construction.
* **Integrity manifest** — at commit time every file's size + SHA-256 is
  recorded in ``manifest.dtp.json`` inside the checkpoint. ``validate``
  re-hashes on load; torn writes, flipped bits, and deleted files all raise
  :class:`CorruptCheckpointError` instead of feeding garbage to a restore.
* **Bounded retry** — transient write failures (``OSError``, including
  injected :class:`~distributed_training_pytorch_tpu.fault.InjectedFault`)
  are retried ``save_retries`` times with exponential backoff before a save
  is declared failed.
* **Newest-valid fallback** — :meth:`restore_latest_valid` walks committed
  checkpoints newest-first and restores the first that passes validation,
  so a corrupt ``last`` degrades to the previous good snapshot instead of
  killing the resume.

TPU-native differences: saving is a *collective* (every process calls
``save``; Orbax coordinates the single metadata write) so the reference's
rank-0 + barrier choreography (``trainer/trainer.py:163-172``) disappears, and
saves may run async so the step loop is not blocked on filesystem I/O.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import orbax.checkpoint as ocp

BEST = "best"
LAST = "last"

MANIFEST_NAME = "manifest.dtp.json"
_STAGING_DIR = ".staging"
_OLD_SUFFIX = ".old"


class CheckpointError(RuntimeError):
    """A save failed permanently (every retry exhausted)."""


class CorruptCheckpointError(CheckpointError):
    """A checkpoint on disk fails integrity validation."""


def epoch_checkpoint_name(epoch: int) -> str:
    """``checkpoint_epoch_{N}`` — the periodic-save name at ``trainer/trainer.py:166``."""
    return f"checkpoint_epoch_{epoch}"


def _is_typed_key(x) -> bool:
    try:
        return jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
    except (AttributeError, TypeError):
        return False


class CheckpointManager:
    """Save/restore named checkpoints of a ``TrainState`` under ``directory``.

    ``save_best_for=(metric_name, mode)`` with mode ``"geq"`` or ``"leq"``
    mirrors the reference's best-fitness rule (``trainer/trainer.py:118-124``,
    configured ``("accuracy", "geq")`` at ``main.py:18``): ``geq`` saves when
    the new value is >= the best seen, ``leq`` when <=.

    ``save_retries``/``retry_backoff`` bound recovery from transient write
    failures; ``fault_plan`` wires a
    :class:`~distributed_training_pytorch_tpu.fault.FaultPlan` into the
    write path (test-only; production leaves it ``None``).
    """

    def __init__(
        self,
        directory: str | os.PathLike,
        *,
        save_best_for: tuple[str, str] | None = None,
        async_save: bool = True,
        max_to_keep: int | None = None,
        save_retries: int = 2,
        retry_backoff: float = 0.25,
        fault_plan=None,
    ):
        self.directory = os.path.abspath(os.fspath(directory))
        if save_best_for is not None:
            metric, mode = save_best_for
            if mode not in ("geq", "leq"):
                raise ValueError(f"save_best_for mode must be 'geq' or 'leq', got {mode!r}")
        self.save_best_for = save_best_for
        # Retention for the PERIODIC checkpoints only (checkpoint_epoch_N):
        # keep the newest `max_to_keep`, delete older ones after each commit.
        # `best`/`last` are policy names, never garbage-collected. Deletion
        # runs on process 0 (shared-filesystem assumption, same as Orbax's).
        self.max_to_keep = max_to_keep
        self.save_retries = int(save_retries)
        self.retry_backoff = float(retry_backoff)
        self.fault_plan = fault_plan
        # Optional telemetry EventLog (duck-typed: anything with .emit).
        # restore_latest_valid reports each checkpoint it rejects while
        # scanning backward through it, so recovery skips land in the JSONL
        # flight record instead of only in free-text logger lines. The
        # trainer assigns it after constructing its event log; None (the
        # default) keeps the manager telemetry-free.
        self.event_log = None
        self._best_value: float | None = None
        self._staging_seq = 0
        # (staging_path, final_name, composite_args) of the in-flight save;
        # commit happens at the next wait()/save()/restore() boundary.
        self._pending: tuple[str, str, Any] | None = None
        if jax.process_index() == 0:
            os.makedirs(self.directory, exist_ok=True)
            self._recover_crash_leftovers()
        handler = ocp.CompositeCheckpointHandler()
        self._ckptr = (
            ocp.AsyncCheckpointer(handler) if async_save else ocp.Checkpointer(handler)
        )

    # -- paths -------------------------------------------------------------

    def path(self, name: str) -> str:
        return os.path.join(self.directory, name)

    def exists(self, name: str) -> bool:
        # A checkpoint is complete once the staging dir has been renamed onto
        # the final name; an in-flight async save is not yet visible.
        return os.path.isdir(self.path(name))

    def checkpoint_names(self) -> list[str]:
        """Committed checkpoint names, newest first (by directory mtime)."""
        found = []
        try:
            entries = os.listdir(self.directory)
        except FileNotFoundError:
            return []
        for entry in entries:
            if entry.startswith(".") or entry.endswith(_OLD_SUFFIX):
                continue
            p = self.path(entry)
            if os.path.isdir(p):
                found.append((os.path.getmtime(p), entry))
        found.sort(reverse=True)
        return [name for _, name in found]

    def _new_staging(self, name: str) -> str:
        self._staging_seq += 1
        return os.path.join(self.directory, _STAGING_DIR, f"{name}.{self._staging_seq}")

    def _recover_crash_leftovers(self) -> None:
        """Repair the crash windows: a half-finished swap (``<name>.old``
        present), and staging dirs from saves that never committed. A staging
        dir that exists under its plain ``<name>.<seq>`` name holds a COMPLETE
        write (Orbax renames its own tmp dir there only on finish) — e.g. an
        async save whose process died between write-finish and the next
        wait(); such checkpoints are promoted, not discarded."""
        for entry in os.listdir(self.directory):
            if not entry.endswith(_OLD_SUFFIX):
                continue
            old_path = self.path(entry)
            if not os.path.isdir(old_path):
                continue
            final = self.path(entry[: -len(_OLD_SUFFIX)])
            if os.path.isdir(final):
                # crash after the new checkpoint landed: old copy is garbage
                shutil.rmtree(old_path, ignore_errors=True)
            else:
                # crash between the two renames: roll the old copy back
                os.rename(old_path, final)
        staging_root = os.path.join(self.directory, _STAGING_DIR)
        if os.path.isdir(staging_root):
            for entry in sorted(os.listdir(staging_root)):
                path = os.path.join(staging_root, entry)
                # Orbax in-flight tmp dirs (write never finished) stay garbage.
                if not os.path.isdir(path) or "orbax" in entry.lower():
                    continue
                name = entry.rsplit(".", 1)[0]
                final = self.path(name)
                if os.path.isdir(final):
                    continue  # never clobber a committed checkpoint
                try:
                    self._write_manifest(path)
                    os.rename(path, final)
                except OSError:
                    pass  # unreadable leftovers are swept below
            shutil.rmtree(staging_root, ignore_errors=True)

    # -- save --------------------------------------------------------------

    def save(
        self,
        name: str,
        state: Any,
        epoch: int,
        metrics: Mapping | None = None,
        loop_state: Mapping | None = None,
        telemetry: Mapping | None = None,
        sharding: Mapping | None = None,
        data_state: Mapping | None = None,
    ) -> None:
        """Collective save of ``state`` + meta under ``directory/name``.

        ``epoch`` is stored as the *resume* epoch — the caller passes the next
        epoch to train, matching the reference storing ``epoch + 1`` for
        ``last`` and ``epoch`` for ``best`` (``trainer/trainer.py:87,124,165``
        — the asymmetry is the caller's policy, not the store's).

        ``loop_state`` carries mid-epoch resume info (e.g. ``step_in_epoch``
        for a preemption save) into the meta json, so a resumed run can skip
        already-trained batches and stay bit-exact with an uninterrupted one.

        ``telemetry`` carries cumulative run-accounting counters (the
        trainer's goodput buckets, ``telemetry/goodput.py``) into the meta
        json the same way — json round-trips Python floats exactly, so a
        resumed run's counters are bit-identical to the saved ones.

        ``sharding`` is the state's sharding-metadata record
        (``parallel.sharding.sharding_record``: mesh axis sizes + the
        PartitionSpec of every sharded leaf). When None it is derived from
        ``state``'s live leaves — callers whose state was already
        snapshotted to host numpy (the async saver) pass the record they
        captured from the live arrays, because ``device_get`` strips
        shardings. Orbax writes the GLOBAL array either way (every process
        contributes its addressable shards); the record documents the
        layout the run trained in, and lets a restore into a different mesh
        be detected and logged as a resharding restore
        (docs/parallelism.md).

        ``data_state`` is a resumable reader's checkpoint-carried state (a
        JSON-able dict from the loader's ``reader_state()`` — docs/data.md:
        epoch, global record cursor, shuffle seed, shard structure).
        It rides as its own ``data/`` composite item under the same rule as
        the loss-scale item: present only when the run streams, and a
        missing item means "fresh cursor" — so pre-streaming checkpoints,
        non-streaming runs, and streaming runs all restore against any
        target (:meth:`read_data_state`).
        """
        self.wait()  # a name may be overwritten; finish any in-flight save first
        self._gc_periodic()  # previous save is committed; safe to prune now
        meta = {"epoch": int(epoch), "best_value": self._best_value}
        # Record the param tree's top level so consumers can auto-select the
        # restore target's wrapper layout (e.g. whether params nest under
        # InputNormalizer's 'inner' scope — ADVICE r4: keying that on a
        # mutable env var across train/resume/eval was a foot-gun).
        try:
            meta["params_top_level"] = sorted(state.params.keys())
        except AttributeError:
            pass
        if metrics is not None:
            meta["metrics"] = {k: float(v) for k, v in metrics.items()}
        if loop_state is not None:
            meta["loop"] = {k: int(v) for k, v in loop_state.items()}
        if telemetry is not None:
            meta["telemetry"] = dict(telemetry)
            # Attempt provenance (ISSUE 16): the restart generation that
            # wrote this checkpoint rides the telemetry mapping from the
            # trainer but is hoisted to a first-class meta field — "which
            # attempt produced the state I'm about to resume from?" is a
            # recovery question, not a goodput-accounting one, and hoisting
            # keeps every save path's signature unchanged.
            if "attempt" in meta["telemetry"]:
                meta["attempt"] = int(meta["telemetry"].pop("attempt"))
        if sharding is None:
            from distributed_training_pytorch_tpu.parallel.sharding import (
                sharding_record,
            )

            sharding = sharding_record(state)
        if sharding is not None:
            meta["sharding"] = dict(sharding)
        # Typed PRNG keys carry an extended dtype serializers reject; store
        # the raw key words + impl name and rebuild on restore (this is also
        # what makes params_only restores work across PRNG impls — key
        # widths differ: threefry 2 words, rbg 4).
        rest = {"step": state.step, "model_state": state.model_state}
        if _is_typed_key(state.rng):
            rest["rng_data"] = jax.random.key_data(state.rng)
            meta["rng_impl"] = str(jax.random.key_impl(state.rng))
        else:
            rest["rng_data"] = state.rng
            meta["rng_impl"] = None
        # Decomposed layout (params / opt_state / rest [/ scale]) — the analog
        # of the reference saving model/optimizer/scheduler state dicts as
        # separate keys (``trainer/trainer.py:85-92``); it also lets consumers
        # that only need weights (offline eval) restore params alone even when
        # their optimizer differs from the training one.
        items = {
            "params": ocp.args.StandardSave(state.params),
            "opt_state": ocp.args.StandardSave(state.opt_state),
            "rest": ocp.args.StandardSave(rest),
        }
        # Mixed-precision loss-scale state (precision.loss_scale) rides as its
        # OWN composite item, present only when it has array leaves (a
        # DynamicScale; None/NoOpScale states save the pre-precision layout
        # verbatim) — so pre-precision checkpoints, fp32 checkpoints, and
        # fp16 checkpoints all restore against any target: a missing item
        # means "keep the target's fresh default scale".
        scale_state = getattr(state, "loss_scale", None)
        if jax.tree.leaves(scale_state):
            from flax import serialization

            items["scale"] = ocp.args.StandardSave(
                serialization.to_state_dict(scale_state)
            )
            meta["loss_scale"] = type(scale_state).__name__
        if data_state:
            items["data"] = ocp.args.JsonSave(dict(data_state))
        args = ocp.args.Composite(meta=ocp.args.JsonSave(meta), **items)
        staging = self._new_staging(name)
        try:
            self._attempt_save(staging, args, blocking=False)
        except OSError as e:
            self._pending = (staging, name, args)
            self._retry_pending(e)
            return
        self._pending = (staging, name, args)
        if not isinstance(self._ckptr, ocp.AsyncCheckpointer):
            self._finalize_pending()

    def _attempt_save(self, staging: str, args, *, blocking: bool) -> None:
        if self.fault_plan is not None:
            self.fault_plan.maybe_raise("checkpoint_write")
        self._ckptr.save(staging, args=args, force=True)
        if blocking and isinstance(self._ckptr, ocp.AsyncCheckpointer):
            self._ckptr.wait_until_finished()

    def _retry_pending(self, first_error: BaseException) -> None:
        """Blocking retry of the pending save with exponential backoff;
        commits on success, raises :class:`CheckpointError` when exhausted."""
        staging, name, args = self._pending
        self._pending = None
        err: BaseException = first_error
        delay = self.retry_backoff
        for _ in range(self.save_retries):
            shutil.rmtree(staging, ignore_errors=True)
            time.sleep(delay)
            delay *= 2
            staging = self._new_staging(name)
            try:
                self._attempt_save(staging, args, blocking=True)
            except OSError as e:
                err = e
                continue
            self._commit(staging, name)
            self._commit_barrier()
            return
        shutil.rmtree(staging, ignore_errors=True)
        # Failure must still reach the commit barrier: peers whose local
        # write succeeded are already waiting in it — raising without
        # aligning would deadlock every other host.
        self._commit_barrier()
        raise CheckpointError(
            f"checkpoint save of {name!r} failed after {self.save_retries + 1} attempts"
        ) from err

    def _finalize_pending(self) -> None:
        """Drive the in-flight save to a committed (or failed) end state.

        For async saves the commit (manifest + rename) runs at the next
        manager call rather than from Orbax's background thread — a write
        that finished mid-epoch sits complete-but-uncommitted in .staging
        until then. A crash in that window does NOT lose it: recovery
        promotes completed staging dirs (see ``_recover_crash_leftovers``).
        """
        if self._pending is None:
            return
        staging, name, args = self._pending
        if isinstance(self._ckptr, ocp.AsyncCheckpointer):
            try:
                self._ckptr.wait_until_finished()
            except OSError as e:
                self._retry_pending(e)
                return
        self._commit(staging, name)
        self._pending = None
        self._commit_barrier()

    def _commit_barrier(self) -> None:
        """Multi-host alignment: a non-zero process must not observe its
        wait() returning before process 0's staging→final rename has
        happened (exists()/restore() right after a collective save would
        otherwise race the commit). Saves are collective, so every process
        reaches this barrier exactly once per finalized save."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("dtp_checkpoint_commit")

    def _commit(self, staging: str, name: str) -> None:
        """Manifest + atomic swap. The final name flips from old checkpoint
        (or absent) to fully-written new checkpoint in one rename."""
        if jax.process_index() == 0:
            self._write_manifest(staging)
            final = self.path(name)
            old = final + _OLD_SUFFIX
            if os.path.isdir(final):
                if os.path.isdir(old):
                    shutil.rmtree(old)
                os.rename(final, old)
            os.rename(staging, final)
            # Persist the rename itself (manifest file data is fsync'd at
            # write; payload durability is the writer's concern) — without
            # this a power loss can resurrect the pre-rename directory view.
            dirfd = os.open(self.directory, os.O_RDONLY)
            try:
                os.fsync(dirfd)
            finally:
                os.close(dirfd)
            shutil.rmtree(old, ignore_errors=True)
            if self.fault_plan is not None:
                ev = self.fault_plan.fires("corrupt_checkpoint")
                if ev is not None:
                    from distributed_training_pytorch_tpu.fault.inject import (
                        corrupt_checkpoint,
                    )

                    corrupt_checkpoint(final, mode=ev.payload or "truncate")

    def _write_manifest(self, staging: str) -> None:
        entries = {}
        for dirpath, _, files in os.walk(staging):
            for fname in files:
                fp = os.path.join(dirpath, fname)
                rel = os.path.relpath(fp, staging)
                if rel == MANIFEST_NAME:
                    continue
                digest = hashlib.sha256()
                with open(fp, "rb") as f:
                    for chunk in iter(lambda: f.read(1 << 20), b""):
                        digest.update(chunk)
                entries[rel] = {
                    "size": os.path.getsize(fp),
                    "sha256": digest.hexdigest(),
                }
        with open(os.path.join(staging, MANIFEST_NAME), "w") as f:  # jaxlint: disable=file-write-without-rank-gate -- both call sites are process_index()==0-gated (save path and ctor crash recovery); the gate is one frame up, outside this helper's lexical scope
            json.dump({"version": 1, "files": entries}, f)
            f.flush()
            os.fsync(f.fileno())

    def best_improved(self, metrics: Mapping) -> bool:
        """Apply the best-fitness rule and record a new best value — WITHOUT
        saving. Split from :meth:`maybe_save_best` so the async save path
        (``resilience.AsyncCheckpointSaver.maybe_save_best``) can evaluate
        the rule on-thread and route the save through its own queue."""
        if self.save_best_for is None:
            return False
        metric, mode = self.save_best_for
        if metric not in metrics:
            raise KeyError(
                f"save_best_for metric {metric!r} not in validation metrics {list(metrics)}"
            )
        value = float(metrics[metric])
        improved = (
            self._best_value is None
            or (mode == "geq" and value >= self._best_value)
            or (mode == "leq" and value <= self._best_value)
        )
        if improved:
            self._best_value = value
        return improved

    def maybe_save_best(
        self,
        metrics: Mapping,
        state: Any,
        epoch: int,
        telemetry: Mapping | None = None,
        data_state: Mapping | None = None,
    ) -> bool:
        """Apply the best-fitness rule; save under ``best`` on improvement.

        Returns True when a new best was saved (``trainer/trainer.py:118-130``).
        """
        if not self.best_improved(metrics):
            return False
        self.save(
            BEST, state, epoch, metrics=metrics, telemetry=telemetry,
            data_state=data_state,
        )
        return True

    # -- integrity ---------------------------------------------------------

    def validate(self, name_or_path: str) -> None:
        """Verify the checkpoint against its integrity manifest.

        Raises :class:`CorruptCheckpointError` on a missing manifest, a
        missing/extra-truncated file, a size mismatch, or a hash mismatch —
        i.e. on every artifact a torn write or bit rot can produce.
        """
        self.wait()
        path = self._resolve(name_or_path)
        manifest_path = os.path.join(path, MANIFEST_NAME)
        if not os.path.isfile(manifest_path):
            raise CorruptCheckpointError(
                f"{path}: no integrity manifest ({MANIFEST_NAME}) — checkpoint "
                "was not committed by this manager or the commit was torn"
            )
        try:
            with open(manifest_path, encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            raise CorruptCheckpointError(f"{path}: unreadable manifest: {e}") from e
        for rel, want in manifest.get("files", {}).items():
            fp = os.path.join(path, rel)
            if not os.path.isfile(fp):
                raise CorruptCheckpointError(f"{path}: missing file {rel}")
            size = os.path.getsize(fp)
            if size != want["size"]:
                raise CorruptCheckpointError(
                    f"{path}: {rel} is {size} bytes, manifest says {want['size']} "
                    "(torn write)"
                )
            digest = hashlib.sha256()
            with open(fp, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    digest.update(chunk)
            if digest.hexdigest() != want["sha256"]:
                raise CorruptCheckpointError(f"{path}: {rel} content hash mismatch")

    def is_valid(self, name_or_path: str) -> bool:
        try:
            self.validate(name_or_path)
            return True
        except (CorruptCheckpointError, FileNotFoundError, ValueError):
            return False

    # -- restore -----------------------------------------------------------

    def restore(
        self,
        name_or_path: str,
        target_state: Any,
        *,
        params_only: bool = False,
        validate: bool = True,
        allow_topology_change: bool = False,
    ) -> tuple[Any, int]:
        """Restore ``(state, resume_epoch)`` from a named checkpoint or path.

        ``target_state`` is a concrete or abstract ``TrainState`` whose
        structure/shardings define the restore layout — the analog of calling
        ``_load_snapshot`` after ``build_model`` so keys line up
        (``trainer/trainer.py:44-45,96-101``).

        ``params_only=True`` restores weights and model_state but keeps the
        target's optimizer state/step — for consumers (offline eval,
        fine-tuning) whose optimizer differs from the training run's.

        ``validate=False`` skips the integrity check (reading a checkpoint
        produced by an external Orbax writer with no manifest).

        A sharded checkpoint whose recorded mesh covers a different device
        count than this backend raises
        :class:`~distributed_training_pytorch_tpu.parallel.elastic.
        TopologyMismatchError` up front, naming both topologies — instead of
        failing deep inside orbax with no mention of topology.
        ``allow_topology_change=True`` proceeds (the elastic-restore path:
        the caller has laid ``target_state`` out for the *current* backend,
        e.g. via ``parallel.elastic.replan`` — the Trainer does this
        automatically); the stored global arrays then relay into the
        target's shardings exactly as any resharding restore does.

        Checkpoints written before the crash-consistency upgrade (no
        ``rng_impl`` in meta, rng stored as a key array under ``rest.rng``,
        no manifest) still restore: their rest tree is read as stored and
        validation is skipped for the manifest they never had.
        """
        self.wait()  # an in-flight async save only becomes visible once committed
        path = self._resolve(name_or_path)
        has_manifest = os.path.isfile(os.path.join(path, MANIFEST_NAME))
        if validate and has_manifest:
            # Validate BEFORE any read: a torn meta json must surface as
            # CorruptCheckpointError (hash mismatch), not a raw orbax error.
            self.validate(path)
        try:
            pre_meta = self.read_meta(path)
        except Exception as e:  # orbax raises various things on torn json
            raise CorruptCheckpointError(f"{path}: unreadable meta: {e}") from e
        legacy = "rng_impl" not in pre_meta
        if validate and not has_manifest and not legacy:
            # current-format checkpoint with its manifest gone: torn commit
            self.validate(path)  # raises the canonical no-manifest error
        # Topology seam (ISSUE 12): a recorded mesh whose device product
        # disagrees with the backend must fail HERE with names attached —
        # not as an opaque orbax sharding-deserialization error — unless the
        # caller explicitly opted into the elastic path. A record-less
        # checkpoint (pure DP / pre-sharding) has no topology to validate:
        # its global arrays restore onto any backend.
        topo_changed = False
        record = pre_meta.get("sharding")
        if record:
            from distributed_training_pytorch_tpu.parallel.elastic import (
                TopologyMismatchError,
                validate_topology,
            )

            try:
                validate_topology(
                    record,
                    jax.device_count(),
                    name=f"checkpoint {os.path.basename(path)!r}",
                )
            except TopologyMismatchError:
                topo_changed = True
                if not allow_topology_change:
                    raise
        # to_shape_dtype_struct preserves each live leaf's NamedSharding, so
        # the restore target's layout — replicated for DP, fsdp/tensor
        # shards otherwise — drives where orbax lays the bytes. That is what
        # makes restore RESHARDING-CAPABLE: a checkpoint written under one
        # mesh restores into any other (DP <-> FSDP both directions,
        # test-enforced) because orbax reads the stored global array and
        # places the target's shards, whatever the writer's layout was.
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target_state)
        self._note_reshard(name_or_path, pre_meta, target_state)
        items = {
            "params": ocp.args.StandardRestore(abstract.params),
            "meta": ocp.args.JsonRestore(),
        }
        if (params_only and not topo_changed) or legacy:
            # Restore `rest` as stored (no target structure): params_only
            # consumes only its model_state, and a legacy rest tree has a
            # different key layout than the current target would impose.
            # On a topology-changed restore the as-stored read is the one
            # path that WOULD die deep in orbax (the stored sharding files
            # name the writer's devices), so params_only then takes the
            # targeted branch below — trading the cross-PRNG-impl width
            # leniency (a same-topology-only concern) for restorability.
            items["rest"] = ocp.args.StandardRestore()
        else:
            # rng is stored as raw key words; recover their aval from the
            # target's key (works across impls of the same width; differing
            # widths restore shape-as-stored below). eval_shape strips the
            # sharding, so it is re-attached from the target key — without
            # it orbax falls back to the checkpoint's sharding file, which
            # is exactly wrong on a resharding restore.
            rng_data = jax.eval_shape(
                lambda k: jax.random.key_data(k) if _is_typed_key(k) else k,
                abstract.rng,
            )
            rng_sharding = getattr(target_state.rng, "sharding", None)
            if isinstance(rng_sharding, jax.sharding.NamedSharding):
                rng_data = jax.ShapeDtypeStruct(
                    rng_data.shape,
                    rng_data.dtype,
                    sharding=jax.sharding.NamedSharding(
                        rng_sharding.mesh, jax.sharding.PartitionSpec()
                    ),
                )
            items["rest"] = ocp.args.StandardRestore(
                {
                    "step": abstract.step,
                    "model_state": abstract.model_state,
                    "rng_data": rng_data,
                }
            )
        if not params_only:
            items["opt_state"] = ocp.args.StandardRestore(abstract.opt_state)
        # Loss-scale state: restored only when BOTH sides speak it — the
        # checkpoint carries a `scale` item AND the target state has scale
        # leaves to lay it into. A pre-precision (or fp32) checkpoint under
        # a dynamic-scale target leaves the target's fresh default in place;
        # a dynamic-scale checkpoint under an fp32 target drops the scale.
        target_scale = getattr(target_state, "loss_scale", None)
        restore_scale = (
            not params_only
            and bool(jax.tree.leaves(target_scale))
            and os.path.isdir(os.path.join(path, "scale"))
        )
        if restore_scale:
            from flax import serialization

            items["scale"] = ocp.args.StandardRestore(
                serialization.to_state_dict(abstract.loss_scale)
            )
        restored = self._ckptr.restore(path, args=ocp.args.Composite(**items))
        meta = restored.meta or {}
        if meta.get("best_value") is not None:
            self._best_value = float(meta["best_value"])
        state = target_state.replace(
            params=restored.params,
            model_state=restored.rest["model_state"],
        )
        if not params_only:
            rng = self._restored_rng(restored.rest, meta, target_state.rng)
            state = state.replace(
                opt_state=restored.opt_state,
                step=restored.rest["step"],
                rng=rng,
            )
        if restore_scale:
            from flax import serialization

            state = state.replace(
                loss_scale=serialization.from_state_dict(target_scale, restored.scale)
            )
        return state, int(meta.get("epoch", 0))

    def _note_reshard(self, name: str, pre_meta: Mapping, target_state: Any) -> None:
        """Detect a resharding restore — the checkpoint's recorded layout
        differs from the restore target's — and put it in the flight record
        (``checkpoint_reshard`` event; docs/observability.md). Detection
        only: the relayout itself is orbax's restore doing its normal job
        against the target shardings. A missing stored record means pure-DP
        / pre-sharding — restoring THAT into a sharded target (or a sharded
        checkpoint into a DP target) is the DP<->FSDP elasticity path and
        is still logged."""
        if self.event_log is None:
            return
        from distributed_training_pytorch_tpu.parallel.sharding import (
            sharding_record,
        )

        stored = pre_meta.get("sharding")
        target = sharding_record(target_state)
        if stored == target:
            return
        self.event_log.emit(
            "checkpoint_reshard",
            name=os.path.basename(str(name)),
            from_mesh=(stored or {}).get("mesh"),
            to_mesh=(target or {}).get("mesh"),
            from_sharded_leaves=len((stored or {}).get("specs", {})),
            to_sharded_leaves=len((target or {}).get("specs", {})),
        )

    @staticmethod
    def _restored_rng(rest: Mapping, meta: Mapping, target_rng):
        """Rebuild the PRNG key from either storage format: current (raw key
        words under ``rng_data`` + impl in meta) or legacy (key array under
        ``rng``, possibly deserialized as raw words)."""
        if "rng_data" in rest:
            impl = meta.get("rng_impl")
            data = rest["rng_data"]
            return jax.random.wrap_key_data(jnp.asarray(data), impl=impl) if impl else data
        rng = rest["rng"]
        if _is_typed_key(target_rng) and not _is_typed_key(rng):
            try:
                rng = jax.random.wrap_key_data(
                    jnp.asarray(rng), impl=str(jax.random.key_impl(target_rng))
                )
            except (TypeError, ValueError):
                pass  # width mismatch: hand back as stored
        return rng

    def latest_valid_name(self) -> "str | None":
        """The name ``restore_latest_valid`` would restore — the newest
        committed checkpoint passing integrity validation, or None when no
        valid checkpoint exists. Lets consumers (the trainer's elastic-resume
        peek) inspect the resume checkpoint's meta BEFORE building a restore
        target, with exactly the fallback-past-corruption choice the real
        restore will make; rejected checkpoints emit ``checkpoint_rejected``
        the same way."""
        self.wait()
        return self._latest_valid_name([])

    def _latest_valid_name(self, skipped: list) -> "str | None":
        for name in self.checkpoint_names():
            try:
                self.validate(name)
            except (CorruptCheckpointError, FileNotFoundError, ValueError) as e:
                skipped.append(name)
                if self.event_log is not None:
                    # Recovery skips become flight-record facts (ISSUE 5):
                    # a torn preemption save silently degrading the resume
                    # to an older snapshot is visible in the JSONL log, not
                    # only in logger text.
                    self.event_log.emit(
                        "checkpoint_rejected", name=name, reason=str(e)
                    )
                continue
            return name
        return None

    def restore_latest_valid(
        self,
        target_state: Any,
        *,
        params_only: bool = False,
        allow_topology_change: bool = False,
    ) -> tuple[Any, int, str]:
        """Restore from the newest checkpoint that passes validation.

        Walks committed checkpoints newest-first; a corrupt ``last`` (torn
        preemption save, bit rot) falls back to the previous good snapshot
        instead of crashing the resume. Returns ``(state, epoch, name)``;
        raises :class:`CheckpointError` when nothing valid remains.
        """
        self.wait()
        skipped: list = []
        name = self._latest_valid_name(skipped)
        if name is None:
            raise CheckpointError(
                f"no valid checkpoint under {self.directory} "
                f"(invalid/corrupt: {skipped or 'none found'})"
            )
        # validate=False: _latest_valid_name just hashed every file;
        # re-validating inside restore would double the resume path's disk
        # reads.
        state, epoch = self.restore(
            name,
            target_state,
            params_only=params_only,
            validate=False,
            allow_topology_change=allow_topology_change,
        )
        return state, epoch, name

    def _resolve(self, name_or_path: str) -> str:
        """Name-or-path -> absolute checkpoint dir, with the existence and
        pre-0.1-layout checks every reader needs."""
        path = self.path(name_or_path) if os.sep not in name_or_path else name_or_path
        path = os.path.abspath(path)  # orbax rejects relative paths
        if not os.path.isdir(path):
            raise FileNotFoundError(f"no checkpoint at {path}")
        if os.path.isdir(os.path.join(path, "state")):
            raise ValueError(
                f"{path} uses the pre-0.1 monolithic 'state' checkpoint layout; "
                "re-save it with this version (decomposed params/opt_state/rest)."
            )
        return path

    def read_meta(self, name_or_path: str) -> dict:
        """The checkpoint's meta json alone (epoch, best_value, metrics,
        params_top_level, loop state) — no state structure needed, so
        consumers can inspect a checkpoint's layout BEFORE building the
        restore target."""
        self.wait()
        restored = self._ckptr.restore(
            self._resolve(name_or_path),
            args=ocp.args.Composite(meta=ocp.args.JsonRestore()),
        )
        return dict(restored.meta or {})

    def read_data_state(self, name_or_path: str) -> "dict | None":
        """The checkpoint's streaming reader state (``data/`` item), or None
        when the checkpoint has none — a pre-streaming checkpoint or a
        non-streaming run. The None IS the contract (the loss-scale item
        rule): a missing item means "fresh cursor", so old checkpoints load
        into streaming runs without fabricating a position."""
        self.wait()
        path = self._resolve(name_or_path)
        # Gate on the item directory like the scale-item restore does:
        # requesting an absent composite item from orbax is an error, not
        # a None.
        if not os.path.isdir(os.path.join(path, "data")):
            return None
        restored = self._ckptr.restore(
            path, args=ocp.args.Composite(data=ocp.args.JsonRestore())
        )
        return dict(restored.data or {})

    # -- lifecycle ---------------------------------------------------------

    @property
    def best_value(self) -> float | None:
        return self._best_value

    def wait(self) -> None:
        """Block until any in-flight save has fully committed (write finished
        AND atomically renamed to its final name)."""
        self._finalize_pending()
        if isinstance(self._ckptr, ocp.AsyncCheckpointer):
            self._ckptr.wait_until_finished()

    def _gc_periodic(self) -> None:
        """Prune committed ``checkpoint_epoch_N`` dirs beyond ``max_to_keep``
        (newest kept). Call only with no save in flight."""
        if self.max_to_keep is None or jax.process_index() != 0:
            return
        import re

        pattern = re.compile(r"^checkpoint_epoch_(\d+)$")
        found = []
        for entry in os.listdir(self.directory):
            match = pattern.match(entry)
            if match and os.path.isdir(self.path(entry)):
                found.append((int(match.group(1)), entry))
        found.sort()
        for _, entry in found[: max(0, len(found) - self.max_to_keep)]:
            shutil.rmtree(self.path(entry), ignore_errors=True)

    def close(self) -> None:
        self.wait()
        self._gc_periodic()
        if jax.process_index() == 0:
            shutil.rmtree(os.path.join(self.directory, _STAGING_DIR), ignore_errors=True)
        self._ckptr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
