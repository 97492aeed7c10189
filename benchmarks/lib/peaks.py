"""Published peaks of the chips this benchmark may run on, keyed by ``device_kind``.

One table, owned by the benchmark. A device that is not in it is an error:
no default, no CPU row. Source of the v5e row: Google Cloud documentation,
"TPU v5e" system architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "interconnect_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add a sourced row to "
            f"benchmarks/lib/peaks.py (known: {sorted(PEAKS)})"
        ) from None
