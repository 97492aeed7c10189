"""Host-sharded batch loader with deterministic global shuffle.

Capability twin of ``DataLoader`` + ``DistributedSampler``
(``trainer/trainer.py:209-217``): global-batch semantics (the user specifies
the *global* batch size, split across hosts — ``trainer/trainer.py:56``),
per-epoch reshuffle via ``set_epoch`` (``:140``), and parallel host-side
loading (``num_workers``, ``:213``).

TPU-first differences:

* the shuffle permutation is seeded by ``(seed, epoch)`` and computed
  identically on every host (fixes the reference's cross-rank shuffle bug,
  SURVEY.md §2e) — host ``p`` takes rows ``[p*L, (p+1)*L)`` of each global
  batch, ``L = global_batch // process_count``;
* batches have **static shape**: training drops the trailing partial batch
  (XLA recompiles on shape change); eval pads the final batch and emits a
  ``"mask"`` weight column so padded rows don't pollute metrics;
* workers are threads, not processes — cv2/numpy release the GIL, and thread
  workers share the page cache with zero pickling overhead.
"""

from __future__ import annotations

import concurrent.futures as cf
import queue
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from distributed_training_pytorch_tpu.data import transforms
from distributed_training_pytorch_tpu.profiling.trace import annotate


class ShardedLoader:
    """Iterate host-local batches ``{field: np.ndarray}`` over a data source.

    ``transform(image, epoch=, index=)`` is applied to the ``"image"`` field of
    each record when provided (a :class:`~.transforms.Compose`).
    """

    def __init__(
        self,
        source,
        global_batch_size: int,
        *,
        shuffle: bool = True,
        seed: int = 0,
        transform: Optional[Callable] = None,
        collate_fn: Optional[Callable] = None,
        num_workers: int = 8,
        prefetch_batches: int = 2,
        drop_last: bool = True,
        pad_final: bool = False,
        process_index: int | None = None,
        process_count: int | None = None,
        skip_corrupt: bool = False,
    ):
        if drop_last and pad_final:
            raise ValueError("drop_last and pad_final are mutually exclusive")
        self.source = source
        # Ref-parity extension point: the reference ctor forwards
        # ``dataset.collate_fn`` to DataLoader (``trainer/trainer.py:59-71``).
        # A collate takes the list of transformed records and returns the
        # batch dict — required when records carry ragged/non-stackable
        # fields. Explicit arg wins; else the source's attribute; else the
        # default field-wise np.stack.
        self.collate_fn = collate_fn if collate_fn is not None else getattr(
            source, "collate_fn", None
        )
        # Same fallback for the transform: sources carry their transform as an
        # attribute (applied by the loader, not __getitem__, so augmentation
        # keys on (epoch, index)); a direct ShardedLoader(source) construction
        # must not silently drop it — un-normalized eval images cost measured
        # accuracy (digits run: 98.3% vs the true 99.4%) while looking fine.
        if transform is None:
            transform = getattr(source, "transform", None)
        self.global_batch_size = int(global_batch_size)
        self.shuffle = shuffle
        self.seed = seed
        self.transform = transform
        self.num_workers = int(num_workers)
        # Host-side look-ahead window: how many *batches* may be in flight
        # (decoding/augmenting) beyond the one being consumed. Distinct from
        # the device-side ``device_prefetch(depth=2)`` ring downstream of the
        # loader (utils/tpu.py): this knob bounds host RAM (window x batch
        # bytes) and decode overlap; that one bounds on-device staging. The
        # defaults compose: 2 host batches decoding while 2 sit on device.
        self.prefetch_batches = max(1, int(prefetch_batches))
        self.drop_last = drop_last
        self.pad_final = pad_final
        # Graceful degradation: a corrupt record (CorruptRecordError, or a
        # decode ValueError) is deterministically replaced by the next
        # readable one and counted in ``corrupt_skipped`` instead of failing
        # the epoch. Sources with their own tolerant batch path (records.py
        # ``skip_corrupt``) get the flag forwarded so the whole-batch fast
        # path degrades the same way — note this SETS the attribute on the
        # caller's source object: don't share one source between a tolerant
        # loader and a strict reader (build a second source over the same
        # shards instead; the footer-index read is cheap).
        self.skip_corrupt = bool(skip_corrupt)
        self._corrupt_skipped = 0
        # Injection seam (ISSUE 13; the FaultPlan/commit_delay_s pattern for
        # the input pipeline): sleep this long in every batch's production
        # path, on the producing thread — a deterministic way to make the
        # loader the bottleneck so the telemetry `data_wait` bucket, the
        # perf gate's data_wait ceiling (scripts/perf_gate.py --data-wait
        # --inject-data-wait), and the run doctor's data_bound verdict can
        # be self-tested against a KNOWN starved pipeline. Production
        # leaves it 0; settable post-construction (loader.load_delay_s=...).
        self.load_delay_s = 0.0
        if skip_corrupt and hasattr(source, "skip_corrupt"):
            source.skip_corrupt = True
        self._epoch = 0
        self._pidx = jax.process_index() if process_index is None else process_index
        self._pcount = jax.process_count() if process_count is None else process_count
        if self.global_batch_size % self._pcount:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{self._pcount} processes"
            )
        self.local_batch_size = self.global_batch_size // self._pcount

    @property
    def corrupt_skipped(self) -> int:
        """Total records skipped as corrupt — loader-level substitutions
        (decode/transform failures) PLUS the source's own tolerant-read count
        (structural corruption handled inside batch fast paths), so callers
        see one number regardless of which layer degraded."""
        return self._corrupt_skipped + int(getattr(self.source, "corrupt_skipped", 0))

    def set_epoch(self, epoch: int) -> None:
        """Reseed the epoch permutation — ``sampler.set_epoch`` analog
        (``trainer/trainer.py:140``)."""
        self._epoch = int(epoch)

    def __len__(self) -> int:
        n = len(self.source)
        if self.drop_last:
            return n // self.global_batch_size
        return -(-n // self.global_batch_size)

    def _global_order(self) -> np.ndarray:
        n = len(self.source)
        if self.shuffle:
            rng = np.random.Generator(
                np.random.Philox(
                    key=transforms.philox_key(self.seed, self._epoch, transforms.SHUFFLE_INDEX)
                )
            )
            return rng.permutation(n)
        return np.arange(n)

    def _load_one_raw(self, index: int, epoch: int) -> dict:
        record = dict(self.source[int(index)])
        if self.transform is not None and "image" in record:
            record["image"] = self.transform(record["image"], epoch=epoch, index=int(index))
        return record

    def _load_one(self, index: int, epoch: int) -> dict:
        if not self.skip_corrupt:
            return self._load_one_raw(index, epoch)
        from distributed_training_pytorch_tpu.data.records import (
            _SKIP_COUNT_LOCK,
            CorruptRecordError,
            tolerant_fetch,
        )

        record, skipped = tolerant_fetch(
            lambda i: self._load_one_raw(i, epoch),
            index,
            len(self.source),
            # decode/transform failures raise plain ValueError too
            exceptions=(CorruptRecordError, ValueError),
        )
        if skipped:
            with _SKIP_COUNT_LOCK:  # worker threads bump this concurrently
                self._corrupt_skipped += skipped
        return record

    def _batch_fast_path(self):
        """Whole-batch production in one call (native C++ runtime): either the
        source loads batches itself (``load_batch``), or it exposes in-memory
        ``arrays`` and the transform is batch-capable (``batch_apply``)."""
        if self.collate_fn is not None:
            # Custom collate implies per-record production — the batch fast
            # paths stack fields themselves, which is exactly what a custom
            # collate exists to replace.
            return None
        if hasattr(self.source, "load_batch"):
            return "source"
        if (
            self.transform is not None
            and hasattr(self.transform, "batch_apply")
            and hasattr(self.source, "arrays")
        ):
            return "arrays"
        return None

    def _maybe_delay(self) -> None:
        if self.load_delay_s:
            import time

            time.sleep(float(self.load_delay_s))  # injection seam (see ctor)

    def _produce_batch(
        self, rows: np.ndarray, mask, epoch: int, fast: str | None, index: int
    ) -> dict:
        """Global batch ``index`` of ``epoch``, whole, on the calling thread
        (a pool worker on the fast paths): one ``loader.batch`` span."""
        with annotate("loader.batch", epoch=epoch, batch=index):
            self._maybe_delay()
            if fast == "source":
                batch = dict(self.source.load_batch(rows, epoch))
            elif fast == "arrays":
                batch = {k: v[rows] for k, v in self.source.arrays.items()}
                if "image" in batch:
                    batch["image"] = self.transform.batch_apply(batch["image"], rows, epoch)
            else:
                records = [self._load_one(i, epoch) for i in rows]
                return self._collate(records, mask)
            if mask is not None:
                batch["mask"] = mask
            return batch

    def _collate(self, records: list[dict], mask: np.ndarray | None) -> dict:
        if self.collate_fn is not None:
            batch = dict(self.collate_fn(records))
        else:
            batch = {k: np.stack([r[k] for r in records]) for k in records[0]}
        if mask is not None:
            # The pad mask stays loader-owned even under a custom collate:
            # padded-row weighting is a loader invariant, not a collate concern.
            batch["mask"] = mask
        return batch

    def global_real_count(self, batch_index: int) -> int:
        """Real (unpadded) rows in global batch ``batch_index`` — identical on
        every host; the correct cross-batch aggregation weight for padded
        validation (each host's local mask sum differs, this does not)."""
        n = len(self.source)
        return max(0, min(self.global_batch_size, n - batch_index * self.global_batch_size))

    def __iter__(self) -> Iterator[dict]:
        return self.iter_batches(0)

    def iter_batches(self, start: int = 0) -> Iterator[dict]:
        """Iterate host-local batches from global batch ``start`` onward.

        ``start > 0`` is the mid-epoch RESUME path: the permutation is a pure
        function of ``(seed, epoch)``, so skipping happens at the index level
        — none of the skipped batches' records are read, decoded, or
        augmented (draining a generator instead would pay the full host
        pipeline for every discarded batch)."""
        order = self._global_order()
        epoch = self._epoch
        num_batches = len(self)
        G = self.global_batch_size
        L = self.local_batch_size

        def batch_indices(b: int) -> tuple[np.ndarray, np.ndarray | None]:
            """This host's row indices for global batch b, plus its slice of
            the global pad mask (None when the loader doesn't pad).

            The final partial batch is padded at the *global* level (repeat
            the last real row up to G) and then sliced per host — every host
            always produces exactly L rows, and the mask is globally
            consistent regardless of how real rows land across hosts."""
            rows = order[b * G : (b + 1) * G]
            mask = None
            if self.pad_final:
                real = len(rows)
                if real < G:
                    rows = np.concatenate([rows, np.repeat(rows[-1:], G - real)])
                mask = (np.arange(G) < real).astype(np.float32)
                mask = mask[self._pidx * L : (self._pidx + 1) * L]
            return rows[self._pidx * L : (self._pidx + 1) * L], mask

        fast = self._batch_fast_path()
        start = max(0, int(start))

        if self.num_workers <= 0:
            for b in range(start, num_batches):
                rows, mask = batch_indices(b)
                yield self._produce_batch(rows, mask, epoch, fast, b)
            return

        # Thread pool with a bounded in-flight window so decode/augment of
        # batch b+1..b+2 overlaps consumption of batch b. Fast-path batches
        # are one future each (the native call is internally multithreaded
        # and GIL-free); the Python path fans out per record.
        with cf.ThreadPoolExecutor(self.num_workers) as pool:
            window: queue.Queue = queue.Queue()
            ahead = self.prefetch_batches

            def submit(b: int):
                rows, mask = batch_indices(b)
                if fast is not None:
                    window.put(
                        (pool.submit(self._produce_batch, rows, mask, epoch, fast, b), None, b)
                    )
                else:
                    futs = [pool.submit(self._load_one, i, epoch) for i in rows]
                    window.put((futs, mask, b))

            upto = min(start + ahead, num_batches)
            for b in range(start, upto):
                submit(b)
            for _ in range(num_batches - start):
                item, mask, b = window.get()
                if upto < num_batches:
                    submit(upto)
                    upto += 1
                if fast is not None:
                    yield item.result()
                else:
                    # Per-record path: the workers decode, this thread waits
                    # for them and collates (the delay seam sits here too).
                    with annotate("loader.batch", epoch=epoch, batch=b):
                        self._maybe_delay()
                        batch = self._collate([f.result() for f in item], mask)
                    yield batch
