"""Forward-only inference engine on the training machinery (ISSUE 18 a).

``InferEngine`` is ``TrainEngine``'s serving twin, built from the same
parts rather than parallel-evolved copies:

* **Per-bucket executable cache + trace accounting.** One compiled
  forward per (batch bucket, per-row signature) — requests pad up to a
  bucket (``serving.batcher.pick_bucket``) so a live traffic mix hits a
  handful of executables, never a compile per observed batch size.
  ``trace_counts`` bumps once per *trace* inside the jitted body, exactly
  the ``TrainEngine`` contract the retrace-guard CI gate pins — a
  dispatch-path change that silently retraces fails the same way here.
* **Sharded like training.** Params lay out through
  ``parallel.sharding.state_shardings`` with the same rule grammar
  (tensor-parallel rules shard a TP serving mesh; a DP mesh replicates),
  batches shard over the data axis via ``parallel.mesh.batch_sharding``,
  outputs gather replicated. No donation: params are read by every
  request, and serving holds no optimizer state to donate.
* **Params from the async saver's manifest.** ``restore_params`` reads a
  named checkpoint (``best`` / ``last``) or the newest valid one through
  ``CheckpointManager.restore(..., params_only=True)`` /
  ``restore_latest_valid`` — the crash-consistent read side of the PR 5
  snapshot->commit protocol, so a torn in-flight commit can never be
  served.
* **Hot-swap by atomic reference flip.** ``swap_params`` installs a new
  ``(version, params)`` pair with one assignment; ``predict`` reads the
  pair once per call. In-flight batches finish on the params they
  started with — a swap never stalls or tears a request
  (docs/serving.md "Hot-swap state machine").
"""

from __future__ import annotations

import threading
import time
from collections import Counter
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.parallel import sharding as sharding_lib
from distributed_training_pytorch_tpu.serving.batcher import pick_bucket

__all__ = ["InferEngine"]


class InferEngine:
    """Compiled forward-only serving engine (see module doc).

    ``apply_fn(params, inputs) -> outputs`` is the pure forward (e.g.
    ``lambda p, x: model.apply({"params": p}, x)``); ``mesh`` the serving
    mesh from ``parallel.mesh.mesh_config_from_spec`` (TP shards the
    model, DP replicates it and shards the batch). Every bucket must
    divide by the mesh's batch-shard extent — checked up front, because
    the error XLA would raise at dispatch time names neither.
    """

    def __init__(
        self,
        apply_fn: Callable[[Any, Any], Any],
        mesh,
        *,
        buckets: tuple = (1, 2, 4, 8),
        sharding_rules: "Sequence | None" = None,
        fsdp_min_size: int = 2**18,
    ):
        self.apply_fn = apply_fn
        self.mesh = mesh
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.sharding_rules = sharding_rules
        self.fsdp_min_size = fsdp_min_size
        extent = mesh_lib.batch_shard_extent(mesh)
        bad = [b for b in self.buckets if b % extent]
        if bad:
            raise ValueError(
                f"buckets {bad} do not divide the mesh's batch-shard extent "
                f"{extent} (mesh {dict(mesh.shape)}): padded batches could "
                "not lay out over the data axis"
            )
        self._batch_sharding = mesh_lib.batch_sharding(mesh)
        self._replicated = NamedSharding(mesh, P())
        # Current params: ONE tuple (version, device params), swapped by a
        # single reference assignment — the GIL makes the read in predict()
        # and the write in swap_params() each atomic, so there is no torn
        # state a request could observe mid-swap.
        self._current: "tuple[str, Any] | None" = None
        self._params_sharding = None
        self._params_structure = None
        # Executable cache: (bucket, per-row shape, dtype) -> compiled fn.
        # jit itself also caches per shape; this dict keeps the engine's
        # closure-per-signature bookkeeping explicit and countable.
        self._executables: dict = {}
        # Bumped once per TRACE inside the compiled body (TrainEngine's
        # retrace-guard contract): steady-state serving re-traces nothing.
        self.trace_counts: Counter = Counter()
        self.swap_count = 0
        self.replan_count = 0
        self._swap_lock = threading.Lock()  # one restore-and-flip at a time

    # -- params ------------------------------------------------------------

    @property
    def params_version(self) -> "str | None":
        cur = self._current
        return cur[0] if cur is not None else None

    def _ambient_mesh(self):
        # Same reason as TrainEngine._ambient_mesh: in-model bare
        # PartitionSpec constraints resolve against the ambient mesh.
        return jax.sharding.set_mesh(self.mesh)

    def _sharding_for(self, params) -> Any:
        leaf_shapes = jax.tree.map(
            lambda x: (tuple(x.shape), str(getattr(x, "dtype", None))), params
        )
        structure = (jax.tree.structure(params), tuple(jax.tree.leaves(leaf_shapes)))
        if self._params_sharding is None:
            self._params_structure = structure
            if self.sharding_rules is None and not any(
                self.mesh.shape.get(a, 1) > 1
                for a in (mesh_lib.FSDP_AXIS, mesh_lib.TENSOR_AXIS)
            ):
                self._params_sharding = self._replicated
            else:
                self._params_sharding = sharding_lib.state_shardings(
                    params,
                    self.mesh,
                    self.sharding_rules or (),
                    fsdp_min_size=self.fsdp_min_size,
                )
        elif structure != self._params_structure:
            raise ValueError(
                "this InferEngine is already bound to a params tree with a "
                "different structure or leaf shapes/dtypes (one engine "
                "serves one model — its executables are compiled against "
                "that layout); build a new engine for the new model."
            )
        return self._params_sharding

    def swap_params(self, params, *, version: str) -> None:
        """Install ``params`` (host or device arrays) as the serving set.
        Lays them out under the engine's sharding, then flips the current
        reference atomically. Compiled executables survive the swap — the
        structure check guarantees the new tree fits them."""
        sharding = self._sharding_for(params)
        placed = jax.device_put(params, sharding)
        # Block until the new params are resident BEFORE flipping, so the
        # first post-swap request never waits on a host->device copy.
        jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            placed,
        )
        self._current = (str(version), placed)
        self.swap_count += 1

    def restore_params(self, manager, target_state, *, name: "str | None" = None) -> str:
        """Load serving params from the async saver's manifest: the named
        checkpoint (``"best"`` / ``"last"``) when given, else the newest
        valid one (``restore_latest_valid`` — PR 5's torn-commit-proof
        fallback). ``target_state`` is an abstract/concrete TrainState
        template defining the restore layout; ``params_only=True`` keeps
        its optimizer untouched (serving has none worth restoring).
        Returns the installed version string ``<name>@e<epoch>``."""
        with self._swap_lock:
            if name is None:
                state, epoch, used = manager.restore_latest_valid(
                    target_state, params_only=True
                )
            else:
                state, epoch = manager.restore(name, target_state, params_only=True)
                used = name
            version = f"{used}@e{epoch}"
            self.swap_params(state.params, version=version)
            return version

    # -- live re-plan --------------------------------------------------------

    def replan_onto(self, mesh) -> None:
        """Rebind the engine to a re-planned ``mesh`` (ISSUE 20): the live
        re-plan half of the drain handshake. Pulls the served params back
        to host, swaps in the new mesh's batch/params shardings, drops
        every compiled executable (they close over the OLD mesh's
        shardings), then re-places the identical param bytes under the new
        layout — the params version does not change, because the bytes do
        not, so responses for identical inputs are bit-identical across
        the re-plan (batch-axis growth never changes per-row math; a
        model-sharding change is refused upstream by the elastic solver).

        Validation happens BEFORE any state is touched: an infeasible
        target (a bucket not dividing the new batch-shard extent) raises
        ``ValueError`` and leaves the engine serving the old plan — the
        handshake's revert path depends on that. The caller must have
        quiesced dispatch first (the server's drain owns that); the swap
        lock here only excludes a concurrent ``restore_params``."""
        extent = mesh_lib.batch_shard_extent(mesh)
        bad = [b for b in self.buckets if b % extent]
        if bad:
            raise ValueError(
                f"buckets {bad} do not divide the re-planned mesh's "
                f"batch-shard extent {extent} (mesh {dict(mesh.shape)}): "
                "cannot re-plan this engine onto that device set"
            )
        with self._swap_lock:
            cur = self._current
            host = None
            if cur is not None:
                version, placed = cur
                host = jax.tree.map(
                    lambda x: np.asarray(jax.device_get(x)), placed
                )
            self.mesh = mesh
            self._batch_sharding = mesh_lib.batch_sharding(mesh)
            self._replicated = NamedSharding(mesh, P())
            self._params_sharding = None
            self._params_structure = None
            self._executables = {}
            self.replan_count += 1
            if host is not None:
                sharding = self._sharding_for(host)
                placed = jax.device_put(host, sharding)
                jax.tree.map(
                    lambda x: (
                        x.block_until_ready()
                        if hasattr(x, "block_until_ready")
                        else x
                    ),
                    placed,
                )
                self._current = (version, placed)

    # -- the compiled forward ----------------------------------------------

    def _forward(self, bucket: int, row_sig: tuple):
        key = (bucket, row_sig)
        fn = self._executables.get(key)
        if fn is None:
            params_sharding = self._params_sharding

            def infer_step(params, batch):
                self.trace_counts["infer_step"] += 1
                return self.apply_fn(params, batch)

            # No donate_argnums: params serve every request and batch rows
            # are caller-owned — nothing here is dead after the call.
            fn = jax.jit(
                infer_step,
                in_shardings=(params_sharding, self._batch_sharding),
                out_shardings=self._replicated,
            )
            self._executables[key] = fn
        return fn

    def predict(self, inputs: np.ndarray) -> "tuple[np.ndarray, str]":
        """Run the forward on ``inputs`` (``[n, ...]`` host array): pads
        ``n`` up to the covering bucket (repeating the last row, so padded
        lanes stay numerically tame), dispatches the cached executable,
        slices the pad back off. Returns ``(outputs[:n], params_version)``
        — the version the batch actually ran on, for response stamping
        across hot-swap boundaries."""
        cur = self._current
        if cur is None:
            raise RuntimeError("InferEngine has no params: call restore_params/swap_params first")
        version, params = cur
        inputs = np.asarray(inputs)
        n = int(inputs.shape[0])
        bucket = pick_bucket(n, self.buckets)
        if bucket != n:
            pad = np.broadcast_to(inputs[-1:], (bucket - n,) + inputs.shape[1:])
            inputs = np.concatenate([inputs, pad], axis=0)
        fn = self._forward(bucket, (inputs.shape[1:], str(inputs.dtype)))
        with self._ambient_mesh():
            batch = jax.device_put(inputs, self._batch_sharding)
            out = fn(params, batch)
        return np.asarray(jax.device_get(out))[:n], version

    def warmup(self, example_row: np.ndarray) -> float:
        """Compile every bucket's executable for one row signature before
        taking traffic (first-request latency must not pay a compile).
        Returns the wall seconds spent."""
        t0 = time.perf_counter()
        for b in self.buckets:
            rows = np.broadcast_to(
                np.asarray(example_row)[None], (b,) + np.asarray(example_row).shape
            )
            self.predict(np.ascontiguousarray(rows))
        return time.perf_counter() - t0
