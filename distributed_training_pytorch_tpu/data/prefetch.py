"""Device prefetch: overlap host batch production with device compute.

The reference's per-step H2D copy is synchronous inside ``train_step``
(``example_trainer.py:70,75`` — and never overlapped despite ``pin_memory``,
SURVEY.md §2e). Here transfers are issued from a background thread ``depth``
batches ahead: ``jax.make_array_from_process_local_data`` starts the async
H2D copy and XLA's scheduler overlaps it with the running step.

The trainer asks for an epoch's input through :func:`epoch_units` and sees
nothing else of this module. Two staging modes share the same
producer/consumer machinery:

* :func:`device_prefetch` — one global batch per item (the single-step loop);
* :func:`device_prefetch_chained` — chain-major: ``chain_steps`` consecutive
  global batches stacked on a new leading axis and shipped as ONE device
  array per window (``parallel.mesh.chain_batch_sharding`` layout), feeding
  the engine's chained train step. Still ``depth`` *windows* in flight, so
  on-device staging memory is bounded by ``depth x chain_steps`` batches.

Spans and counters (profiling/trace.py): the producer stages each unit inside
a ``prefetch.stage`` span (stacking a window and handing it to the device —
where PJRT lays a batch out for the chip on the host). The span closes before
the ``put``, so time blocked on a full ring (back-pressure) is in no span.
The consumer counts ``prefetch.fetches`` and, when the ring held nothing as
it asked, ``prefetch.fetches_empty``.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Iterable, Iterator

import jax
import numpy as np

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.profiling.trace import annotate, count


def _prefetched(items: Iterable, depth: int) -> Iterator:
    """Drive ``items`` from a background thread, ``depth`` results in flight.

    Shutdown contract (both normal exhaustion and an abandoned consumer): the
    producer's ``put`` aborts once ``cancelled`` is set, and the consumer's
    cleanup must release every device buffer parked in the queue. The drain
    below runs *after* signalling ``cancelled``, pulls with ``get_nowait``
    until ``Empty`` (``q.empty()`` is only a snapshot — a producer blocked in
    ``q.put`` can land one more item right after a non-empty check), and
    re-drains once more after ``join``: the producer may have completed a
    final ``put`` between the first drain and its own ``cancelled`` check, and
    a buffer stranded that way would keep ``depth`` device batches live for
    the queue object's lifetime.
    """
    q: queue.Queue = queue.Queue(maxsize=depth)
    _SENTINEL = object()
    err: list[BaseException] = []
    cancelled = threading.Event()

    def producer():
        try:
            for item in items:
                # Bounded put that aborts when the consumer goes away, so an
                # abandoned iterator can't leave this thread (and `depth`
                # device batches) parked on a full queue forever.
                while not cancelled.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if cancelled.is_set():
                    return
        except BaseException as e:  # propagate into the consumer
            err.append(e)
        finally:
            while True:  # sentinel put must not block either
                try:
                    q.put(_SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    if cancelled.is_set():
                        break

    thread = threading.Thread(target=producer, daemon=True, name="device-prefetch")
    thread.start()
    try:
        while True:
            count("prefetch.fetches")
            if q.qsize() == 0:
                count("prefetch.fetches_empty")
            item = q.get()
            if item is _SENTINEL:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        cancelled.set()

        def drain():
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    return

        drain()
        thread.join(timeout=2.0)
        drain()  # a put completed before the producer observed `cancelled`


def _stage(put, host, mesh, ids: dict, steps: int):
    """One unit onto the device inside its ``prefetch.stage`` span; ``ids``
    (``epoch``, ``unit``, ``batch``: where this unit starts) then move on by
    the unit's ``steps``."""
    with annotate("prefetch.stage", steps=steps, **ids):
        staged = put(host, mesh)
    ids["unit"] += steps
    ids["batch"] += steps
    return staged


def _stage_ids(ids: dict | None) -> dict:
    return {"epoch": 0, "unit": 0, "batch": 0, **(ids or {})}


def device_prefetch(
    batches: Iterable[dict],
    mesh: jax.sharding.Mesh,
    *,
    depth: int = 2,
    ids: dict | None = None,
) -> Iterator[dict]:
    """Yield global data-sharded ``jax.Array`` batches, ``depth`` in flight.
    ``ids``: the ``epoch``, and the ``unit`` and ``batch`` of the first batch,
    for the ``prefetch.stage`` spans (default: all 0)."""
    ids = _stage_ids(ids)
    staged = (
        _stage(mesh_lib.global_array_from_host_local, host_batch, mesh, ids, 1)
        for host_batch in batches
    )
    return _prefetched(staged, depth)


def device_prefetch_chained(
    batches: Iterable[dict],
    mesh: jax.sharding.Mesh,
    chain_steps: int,
    *,
    depth: int = 2,
    lead_singles: int = 0,
    ids: dict | None = None,
) -> Iterator[tuple[int, dict]]:
    """Chain-major device staging: yield ``(n, batch)`` execution units.

    ``n == chain_steps``: ``batch`` is a window of ``chain_steps`` consecutive
    global batches stacked on a new leading axis (one
    ``chain_batch_sharding``-laid-out transfer), ready for
    ``TrainEngine.train_steps_chained``. ``n == 1``: ``batch`` is a plain
    single-step global batch — emitted for the first ``lead_singles`` batches
    (the trainer's window-boundary realignment after a mid-epoch resume) and
    for the epoch tail shorter than a
    full window (compiling a fresh chain per tail length would cost a
    full-model retrace; the tail reuses the already-compiled single step).

    ``chain_steps == 1`` degenerates to :func:`device_prefetch` semantics
    (every unit a single), so one consumer loop serves both modes.
    ``ids``: as :func:`device_prefetch`.
    """
    if chain_steps < 1:
        raise ValueError(f"chain_steps must be >= 1, got {chain_steps}")
    ids = _stage_ids(ids)

    def single(host_batch):
        return 1, _stage(mesh_lib.global_array_from_host_local, host_batch, mesh, ids, 1)

    def stack_and_put(window, mesh):
        stacked = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *window
        )
        return mesh_lib.global_chain_array_from_host_local(stacked, mesh)

    def staged():
        it = iter(batches)
        for host_batch in itertools.islice(it, max(0, int(lead_singles))):
            yield single(host_batch)
        while True:
            window = list(itertools.islice(it, chain_steps))
            if not window:
                return
            if len(window) < chain_steps or chain_steps == 1:
                for host_batch in window:
                    yield single(host_batch)
                if len(window) < chain_steps:
                    return
                continue
            yield chain_steps, _stage(stack_and_put, window, mesh, ids, chain_steps)

    return _prefetched(staged(), depth)


def epoch_units(
    loader,
    mesh: jax.sharding.Mesh,
    *,
    chain_steps: int,
    skip_steps: int = 0,
    preprocess: Callable[[dict], dict] | None = None,
    epoch: int = 0,
    first_unit: int = 0,
) -> Iterator[tuple[int, dict]]:
    """One epoch of ``loader`` as device-resident execution units ``(n,
    batch)``: ``n == chain_steps`` a chain-stacked window, ``n == 1`` a plain
    global batch (every unit when ``chain_steps == 1``; else the lead and the
    tail, :func:`device_prefetch_chained`). The ring is built here, per call.

    ``skip_steps``: batches a mid-epoch resume has already trained. They are
    skipped at the loader's index level where it can (``iter_batches``: none
    is read or decoded), else drained and dropped; under chaining the first
    ``-skip_steps % chain_steps`` units are then singles, so that windows sit
    at the same multiples of ``chain_steps`` as in an uninterrupted epoch.
    ``preprocess`` runs on each host batch, on the producer's thread.
    ``epoch`` and ``first_unit`` (the global step of the epoch's first batch)
    are where the ``prefetch.stage`` spans' ids start counting."""
    if skip_steps and hasattr(loader, "iter_batches"):
        batches = loader.iter_batches(skip_steps)
    elif skip_steps:
        batches = itertools.islice(iter(loader), skip_steps, None)
    else:
        batches = iter(loader)
    if preprocess is not None:
        batches = (preprocess(b) for b in batches)
    ids = {"epoch": epoch, "unit": first_unit + skip_steps, "batch": skip_steps}
    if chain_steps > 1:
        return device_prefetch_chained(
            batches, mesh, chain_steps, lead_singles=-skip_steps % chain_steps, ids=ids
        )
    return ((1, b) for b in device_prefetch(batches, mesh, ids=ids))
