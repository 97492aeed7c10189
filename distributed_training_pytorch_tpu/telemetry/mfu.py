"""MFU and roofline fields from compiled-program cost analysis + step time.

Model-FLOPs utilization (the PaLM-paper run metric) is FLOPs-per-second
achieved over the chip's peak: ``flops_per_step / step_time / peak``. The
FLOP numerator can come from three conventions (see ``bench.py``'s module
doc): the analytic layer-formula count, the HLO conv/dot recount
(``utils.hlo_flops.executed_matmul_flops``), or XLA's own
``cost_analysis()``. This module owns the shared pieces — the per-chip peak
table and the ratio — used by both ``bench.py`` (which assembles its three
conventions with measurement-specific rescale guards) and the ``Trainer``'s
telemetry (the ``TrainEngine.step_cost_analysis`` probe, reported per
chained window via :func:`window_report`).
"""

from __future__ import annotations

__all__ = [
    "PEAK_FLOPS",
    "device_peak_flops",
    "mfu_value",
    "throughput_fields",
    "window_report",
]

# Published bf16 peak FLOP/s of one chip, keyed by a substring of PJRT's
# ``device_kind``. Source: Google Cloud TPU documentation, the per-generation
# system-architecture pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s — jax reports that chip as "TPU v5 lite"; "TPU v4": 275; "TPU v5p":
# 459; "TPU v6e": 918). Only the v5e row has been exercised on a chip
# (chip_smoke.py). A device that is not in the table has NO peak: there is no
# CPU row and no default, so no utilisation is ever computed against a
# made-up denominator.
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6": 918e12,
}


def device_peak_flops(device) -> float | None:
    """Published peak bf16 FLOP/s of one device, by ``device_kind`` substring
    match; None for a kind that is not in :data:`PEAK_FLOPS`. Callers that
    report utilisation treat None as "no utilisation field" (the Trainer) or
    as an error (``bench.py``, ``chip_smoke.py``)."""
    kind = str(getattr(device, "device_kind", "")).lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    return None


def mfu_value(
    flops_per_step: float, step_time_s: float, peak_flops: float | None
) -> float | None:
    """``flops / dt / peak`` with the degenerate cases mapped to None (no
    FLOPs known / zero time / no known peak -> no utilization claim)."""
    if not flops_per_step or not step_time_s or not peak_flops:
        return None
    return float(flops_per_step) / float(step_time_s) / float(peak_flops)


def throughput_fields(items_per_sec: float, mesh) -> dict:
    """Per-chip AND per-replica throughput for a mesh run (ISSUE 10).

    On a pure-DP mesh the two divisors agree and per-chip is the whole
    story. On a sharded mesh they do not: ``data=2, tensor=4`` runs TWO
    batch replicas on 8 chips, so dividing by ``mesh.devices.size`` alone
    makes a healthy TP config look 4x slower than DP at identical
    hardware efficiency. The scale-out figure is per batch REPLICA — the
    batch-sharded axes product (``parallel.mesh.batch_shard_extent``),
    data x fsdp, never the raw device count."""
    from distributed_training_pytorch_tpu.parallel.mesh import batch_shard_extent

    n_devices = int(mesh.devices.size)
    replicas = batch_shard_extent(mesh)
    return {
        "items_per_sec_chip": float(items_per_sec) / max(n_devices, 1),
        "items_per_sec_replica": float(items_per_sec) / max(replicas, 1),
        "batch_replicas": replicas,
    }


def window_report(
    steps: int,
    window_time_s: float,
    *,
    flops_per_step: float | None,
    peak_flops: float | None,
) -> dict:
    """Per-window telemetry fields from measured wall time: ``steps``,
    ``step_ms``, and ``mfu`` when a FLOP count is known (the trainer's
    ``step_cost_analysis`` probe or an explicit ``Telemetry(flops_per_step=
    ...)``) and the device has a published peak. A "window" is whatever
    interval the caller timed — under chained execution the trainer's sync
    points land on window boundaries, so the report covers whole windows."""
    steps = max(int(steps), 1)
    step_s = window_time_s / steps
    out = {"steps": steps, "step_ms": step_s * 1e3}
    mfu = mfu_value(flops_per_step or 0.0, step_s, peak_flops)
    if mfu is not None:
        out["mfu"] = mfu
    return out
