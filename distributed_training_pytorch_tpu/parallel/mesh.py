"""Device-mesh bootstrap and sharding helpers.

TPU-native replacement for the reference's process-group layer
(``trainer/trainer.py:74-82`` ``ddp_setup``/``destroy_process`` and the
torchrun env-var rendezvous in ``run.sh:9-14``): instead of
``init_process_group("nccl")`` plus per-rank CUDA device binding, we run
``jax.distributed.initialize`` (coordinator-based rendezvous over DCN) once per
host and build a named :class:`jax.sharding.Mesh` over all global devices.
Collectives then ride ICI/DCN via shardings — there is no NCCL-style tuning
surface (``run.sh:1-8``) because XLA's latency-hiding scheduler owns that.

Mesh axes used throughout the framework:

* ``data``  — data parallelism (the reference's only axis, DDP at
  ``trainer/trainer.py:52``).
* ``fsdp``  — parameter sharding (ZeRO-3 analog), optional.
* ``tensor``— tensor parallelism for wide layers, optional.
* ``seq``   — sequence/context parallelism (ring attention), optional.
* ``pipe``  — pipeline parallelism (``parallel.pipeline``), optional.
* ``expert``— MoE expert parallelism (``parallel.moe``), optional.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names, in mesh order. `data` is outermost so that pure-DP
# meshes are contiguous over ICI and cross-host traffic stays on the data axis;
# `pipe` sits just inside it (stage-to-stage ppermute tolerates DCN hops),
# while `seq`/`tensor` are innermost so their latency-sensitive collectives
# (ring permutes, all-reduces) ride contiguous ICI neighborhoods.
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"
AXIS_ORDER = (DATA_AXIS, FSDP_AXIS, PIPE_AXIS, EXPERT_AXIS, SEQ_AXIS, TENSOR_AXIS)

_initialized = False


def setup_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize multi-host JAX if launched as part of a pod.

    Analog of ``Trainer.ddp_setup`` (``trainer/trainer.py:74-77``) — but a
    no-op on single-process launches (TPU pods discovered via TPU metadata, or
    explicit coordinator env vars mirroring torchrun's MASTER_ADDR/RANK/
    WORLD_SIZE contract from ``run.sh:12-13``).

    Env vars honored (all optional): ``COORDINATOR_ADDRESS``, ``NUM_PROCESSES``,
    ``PROCESS_ID``.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("NUM_PROCESSES"):
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and os.environ.get("PROCESS_ID"):
        process_id = int(os.environ["PROCESS_ID"])
    if coordinator_address is not None or num_processes is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    elif process_id is not None:
        raise ValueError(
            "PROCESS_ID is set but COORDINATOR_ADDRESS/NUM_PROCESSES are not — "
            "a partial distributed config would silently train N independent "
            "single-process worlds. Set all three (or none for single-process)."
        )
    # Single-process (including single-host TPU and CPU tests): nothing to do.


def shutdown_distributed() -> None:
    """Analog of ``destroy_process`` (``trainer/trainer.py:80-82``)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def process_index() -> int:
    """This host's process index (analog of torchrun RANK for hosts)."""
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    """True on process 0 — the only process that writes logs/metadata,
    mirroring the reference's rank-0-only sections (``trainer/trainer.py:115,163``)."""
    return jax.process_index() == 0


def create_mesh(
    axes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name -> size; at most one size may be ``-1`` meaning
    "all remaining devices". Default is a 1-D data mesh over every global
    device — the TPU equivalent of the reference's flat DDP world
    (``trainer/trainer.py:48-52``).
    """
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {DATA_AXIS: -1})
    n = len(devices)
    known = 1
    wildcard = None
    for name, size in axes.items():
        if size == -1:
            if wildcard is not None:
                raise ValueError("at most one mesh axis may be -1")
            wildcard = name
        else:
            known *= size
    if wildcard is not None:
        if n % known:
            raise ValueError(f"{n} devices not divisible by fixed axes {axes}")
        axes[wildcard] = n // known
    total = int(np.prod(list(axes.values())))
    if total != n:
        raise ValueError(f"mesh {axes} needs {total} devices, have {n}")
    unknown = [a for a in axes if a not in AXIS_ORDER]
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; known axes are {AXIS_ORDER}")
    # Canonical ordering keeps `data` outermost regardless of dict order.
    names = sorted(axes, key=AXIS_ORDER.index)
    shape = tuple(axes[name] for name in names)
    device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(device_array, axis_names=tuple(names))


def batch_sharding(mesh: Mesh, batch_axes: Sequence[str] | None = None) -> NamedSharding:
    """Sharding for a batch: leading dim split over the data-like mesh axes.

    Replaces ``DistributedSampler``'s per-rank row assignment
    (``trainer/trainer.py:215``) — the batch is one global ``jax.Array`` whose
    leading axis is sharded over ``data`` (and ``fsdp`` if present).
    """
    if batch_axes is None:
        batch_axes = [a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names]
    spec = P(tuple(batch_axes)) if batch_axes else P()
    return NamedSharding(mesh, spec)


def ambient_batch_axes(rows: int) -> tuple[tuple[str, ...], int]:
    """The batch axes (``data`` x ``fsdp``) of the ambient mesh (the one
    ``TrainEngine`` / ``InferEngine`` set around their jits) that split a
    batch of ``rows``, and how many chips they make. None, and 1, where there
    is no ambient mesh, where they do not divide ``rows`` (the batch-1 example
    input of ``model.init`` stays whole), or inside a manual region, whose
    shapes are a chip's already."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.manual_axes:
        return (), 1
    axes = tuple(a for a in (DATA_AXIS, FSDP_AXIS) if mesh.shape.get(a, 1) > 1)
    chips = math.prod(mesh.shape[a] for a in axes)
    return (axes, chips) if rows % chips == 0 else ((), 1)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def chain_batch_sharding(mesh: Mesh, batch_axes: Sequence[str] | None = None) -> NamedSharding:
    """Sharding for a chain-stacked batch ``[chain, batch, ...]``: the leading
    (step/time) axis stays unsharded — every device sees every step of the
    window — while the second (batch) axis splits over the data-like mesh axes
    exactly as :func:`batch_sharding` does. This is the input layout of the
    engine's chained train step (``TrainEngine.train_steps_chained``), whose
    ``lax.scan`` slices one per-step batch off the leading axis per trip."""
    if batch_axes is None:
        batch_axes = [a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names]
    spec = P(None, tuple(batch_axes)) if batch_axes else P()
    return NamedSharding(mesh, spec)


def device_coords(mesh: Mesh) -> "dict[int, tuple[int, ...]]":
    """Map global device id -> this mesh's axis coordinates (one tuple per
    axis in ``mesh.axis_names`` order). Replica groups in compiled HLO name
    devices by their global ids (``use_global_device_ids``); this map is how
    ``analysis.comm_audit`` attributes a collective's device groups back to
    the mesh axes they span — robust to ``mesh_utils`` device reorderings
    because it reads positions off ``mesh.devices`` itself."""
    coords: dict[int, tuple[int, ...]] = {}
    for idx in np.ndindex(mesh.devices.shape):
        coords[int(mesh.devices[idx].id)] = tuple(int(i) for i in idx)
    return coords


def batch_shard_extent(mesh: Mesh) -> int:
    """How many ways the batch dimension is sharded on ``mesh`` — the
    product of the batch-like axes present (``data`` x ``fsdp``, the axes
    :func:`batch_sharding` splits dim 0 over). This, NOT ``mesh.devices.
    size``, is the divisor for global-batch divisibility checks and
    per-replica throughput math: a ``data=2, tensor=4`` mesh runs 2 batch
    shards on 8 chips — every ``tensor`` group of 4 devices cooperates on
    ONE shard."""
    extent = 1
    for axis in (DATA_AXIS, FSDP_AXIS):
        extent *= int(mesh.shape.get(axis, 1))
    return max(1, extent)


# Mesh-spec grammar (the ``MESH``/``BENCH_MESH`` env-knob syntax; see
# docs/parallelism.md): either concatenated axis-size pairs ("dp2fsdp2tp2"
# -> data=2, fsdp=2, tensor=2) or the two-axis shorthand "<kind>KxD" where K
# is the kind's extent and D the data extent ("fsdp4x2" -> fsdp=4, data=2;
# "tp2x4" -> tensor=2, data=4). "dp8" -> pure 8-way data parallelism.
_SPEC_KINDS = {
    "dp": "data",
    "fsdp": "fsdp",
    "tp": "tensor",
    "sp": "seq",
    "pp": "pipe",
    "ep": "expert",
}
_SPEC_SHORT_RE = re.compile(r"^(fsdp|tp|sp|pp|ep)(\d+)x(\d+)$")
_SPEC_PAIRS_RE = re.compile(r"(fsdp|dp|tp|sp|pp|ep)(\d+)")


def mesh_config_from_spec(spec: str) -> "MeshConfig":
    """Parse a compact mesh spec string into a :class:`MeshConfig`.

    ``"dp8"`` -> 8-way data; ``"fsdp4x2"`` -> fsdp=4, data=2;
    ``"tp2x4"`` -> tensor=2, data=4; ``"dp2fsdp2tp2"`` -> data=2, fsdp=2,
    tensor=2. One grammar shared by the examples' ``MESH`` knob and
    ``bench.py``'s ``BENCH_MESH`` sweep."""
    text = spec.strip().lower()
    if not text:
        raise ValueError("empty mesh spec")
    m = _SPEC_SHORT_RE.match(text)
    if m:
        kind, extent, data = m.group(1), int(m.group(2)), int(m.group(3))
        return MeshConfig(**{"data": data, _SPEC_KINDS[kind]: extent})
    pairs = _SPEC_PAIRS_RE.findall(text)
    if not pairs or "".join(k + n for k, n in pairs) != text:
        raise ValueError(
            f"unparseable mesh spec {spec!r} — use axis-size pairs like "
            "'dp8', 'dp2fsdp2tp2', or the shorthand 'fsdp4x2' / 'tp2x4' "
            "(<kind><extent>x<data>)"
        )
    axes: dict[str, int] = {}
    for kind, n in pairs:
        name = _SPEC_KINDS[kind]
        if name in axes:
            raise ValueError(f"mesh spec {spec!r} names axis {name!r} twice")
        axes[name] = int(n)
    axes.setdefault("data", 1)
    return MeshConfig(**axes)


def mesh_from_env(var: str = "MESH") -> Mesh | None:
    """Resolve the examples' ``MESH`` env knob (docs/parallelism.md
    grammar via :func:`mesh_config_from_spec`) to a built mesh.
    Unset/empty = None = the historical 1-D data mesh — the one
    implementation shared by every example entry so the knob's semantics
    cannot drift between them."""
    spec = os.environ.get(var)
    if not spec:
        return None
    return mesh_config_from_spec(spec).build()


def local_batch_size(global_batch_size: int, mesh: Mesh) -> int:
    """Per-host batch size — global-batch semantics of ``trainer/trainer.py:56``
    (``batch_size // world_size``), except the divisor is host count because
    each host feeds all of its local devices in one global array."""
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(f"global batch {global_batch_size} not divisible by {n} processes")
    return global_batch_size // n


def global_array_from_host_local(batch, mesh: Mesh) -> jax.Array:
    """Assemble a global, data-sharded ``jax.Array`` from this host's slice.

    The TPU analog of DDP's implicit "each rank holds its own batch rows":
    every host passes its local rows; the result is a single global array laid
    out across the mesh without any cross-host copy.
    """
    sharding = batch_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        batch,
    )


def global_chain_array_from_host_local(batch, mesh: Mesh) -> jax.Array:
    """Chain-major twin of :func:`global_array_from_host_local`: every leaf is
    ``[chain, local_batch, ...]`` (this host's rows of ``chain`` consecutive
    global batches stacked on a new leading axis) and assembles into one global
    ``[chain, global_batch, ...]`` array laid out per
    :func:`chain_batch_sharding` — one H2D staging call per window instead of
    one per step."""
    sharding = chain_batch_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        batch,
    )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh spec (used by the config system and ``run.sh`` twin)."""

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        axes = {DATA_AXIS: self.data}
        for name, size in (
            (FSDP_AXIS, self.fsdp),
            (PIPE_AXIS, self.pipe),
            (EXPERT_AXIS, self.expert),
            (SEQ_AXIS, self.seq),
            (TENSOR_AXIS, self.tensor),
        ):
            if size != 1:
                axes[name] = size
        return create_mesh(axes, devices=devices)
