"""Live device-memory telemetry: the ONE ``memory_stats`` read.

Three consumers watch allocator state — ``bench.py``'s per-sweep-entry
memory fields, the trainer's per-window telemetry, and the preflight
layer's capacity lookup — and before this module each grew its own inline
read with its own caveat comments. One implementation, one contract:

* ``device.memory_stats()`` is a host-side PJRT allocator query — **no
  device sync** — so reading it at the trainer's existing ``log_every``
  sync points adds zero host syncs to the hot loop;
* backends without allocator stats (CPU, some plugin paths) return
  ``None``/raise; every helper here **degrades to absent fields** rather
  than fabricating numbers (the events/bench consumers simply omit the
  keys — test-enforced);
* ``peak_bytes`` is the larger of the allocator's ``peak_bytes_in_use`` and
  in-use + reserved (:func:`peak_bytes`: this runtime keeps a program's
  temporaries in ``bytes_reserved``, outside its own peak), a
  **process-lifetime high-water mark** with no reset: in a sweep, only the
  first run's peak describes that run —
  later (smaller) configs would silently report the earlier run's peak,
  which is why ``live_memory_fields(include_peak=False)`` exists and why
  the trainer's window records keep ``live_bytes`` as the per-window
  signal (the growth detector watches it, not the peak).
"""

from __future__ import annotations

import jax

__all__ = [
    "device_capacity_bytes",
    "device_memory_stats",
    "is_oom_error",
    "live_memory_fields",
    "memory_skew",
    "peak_bytes",
    "window_memory_fields",
]


def device_memory_stats(device=None) -> dict | None:
    """``device.memory_stats()`` or None when the backend has none (CPU) —
    the single implementation of the read every consumer shares."""
    if device is None:
        device = jax.local_devices()[0]
    try:
        stats = device.memory_stats()
    except (AttributeError, NotImplementedError, RuntimeError):
        return None
    return dict(stats) if stats else None


def device_capacity_bytes(device=None) -> int | None:
    """Per-device memory capacity (``bytes_limit`` — the allocator's HBM
    budget), or None when the backend reports no stats. The preflight
    layer's denominator."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return int(limit) if limit else None


def peak_bytes(stats: dict) -> int | None:
    """Peak device bytes from one ``memory_stats()`` dict, or None when it
    carries no peak. On today's TPU runtime a running program's temporaries
    sit in ``bytes_reserved`` and never reach ``peak_bytes_in_use`` (PERF.md
    section 3), so where a reserved figure is reported the peak is the larger
    of the allocator's own peak and in-use + reserved — the reckoning of
    ``benchmarks/lib/harness.py:memory_peak_bytes``."""
    if "peak_bytes_in_use" not in stats:
        return None
    in_use = stats.get("bytes_in_use", 0)
    return int(max(
        stats["peak_bytes_in_use"],
        in_use + stats.get("peak_bytes_reserved", 0),
        in_use + stats.get("bytes_reserved", 0),
    ))


def live_memory_fields(device=None, *, include_peak: bool = True) -> dict:
    """``{"live_bytes": ..., "peak_bytes": ...}`` from the allocator, or
    ``{}`` on statless backends. ``include_peak=False`` drops the
    process-lifetime high-water mark (see module docstring) — sweep runs
    after the first must not report the first run's peak as theirs."""
    stats = device_memory_stats(device)
    if not stats:
        return {}
    out = {}
    if "bytes_in_use" in stats:
        out["live_bytes"] = int(stats["bytes_in_use"])
    peak = peak_bytes(stats) if include_peak else None
    if peak is not None:
        out["peak_bytes"] = peak
    return out


def window_memory_fields(devices=None, *, include_peak: bool = True) -> dict:
    """The trainer's per-window record: ONE pass over the local devices
    producing device 0's ``live_bytes``/``peak_bytes`` AND the multi-chip
    ``live_bytes_min/max/skew`` from the same sampling instant — two
    separate reads could land allocations between them and emit a
    self-contradictory record (``live_bytes`` outside its own min/max).
    ``{}`` on statless backends."""
    if devices is None:
        devices = jax.local_devices()
    per_device = [device_memory_stats(d) for d in devices]
    out = {}
    first = per_device[0] if per_device else None
    if first:
        if "bytes_in_use" in first:
            out["live_bytes"] = int(first["bytes_in_use"])
        peak = peak_bytes(first) if include_peak else None
        if peak is not None:
            out["peak_bytes"] = peak
    if len(per_device) >= 2 and all(
        s and "bytes_in_use" in s for s in per_device
    ):
        live = [int(s["bytes_in_use"]) for s in per_device]
        out["live_bytes_min"] = min(live)
        out["live_bytes_max"] = max(live)
        out["live_bytes_skew"] = max(live) - min(live)
    return out


def memory_skew(devices=None) -> dict:
    """Per-chip live-byte skew on multi-chip hosts: ``{"live_bytes_min",
    "live_bytes_max", "live_bytes_skew"}`` (max - min). A data-parallel
    step's live set should be near-identical per chip; persistent skew
    means one chip carries buffers its peers do not (a leaked per-device
    array, an unsharded constant) and will OOM first. ``{}`` on single-chip
    hosts or statless backends. A thin filter over
    :func:`window_memory_fields` — ONE implementation of the multi-device
    pass, one sampling instant."""
    return {
        k: v
        for k, v in window_memory_fields(devices, include_peak=False).items()
        if k.startswith("live_bytes_")
    }


def is_oom_error(err: BaseException) -> bool:
    """Whether an exception is a DEVICE out-of-memory: XLA surfaces
    allocator exhaustion as ``jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED:
    ...")`` — or, from some allocation paths, the same class worded "Out of
    memory ..." without the status name
    (the bench sweep's per-entry net catches exactly this and emits a
    structured ``{"oom": true}`` line instead of killing the sweep).
    Host-side ``MemoryError`` is deliberately NOT classified: the net must
    report fit boundaries the device actually hit — host-RAM exhaustion
    wearing the same name is a bug to surface, not a boundary to record."""
    text = str(err)
    if "RESOURCE_EXHAUSTED" in text:
        return True
    return (
        isinstance(err, jax.errors.JaxRuntimeError)
        and "out of memory" in text.lower()
    )
