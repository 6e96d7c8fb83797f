"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A device missing here is an error, never
a default: a utilization or roofline share against a guessed peak means
nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float   # FLOP/s, dense bf16 matmul
    hbm_bytes_s: float  # B/s, HBM bandwidth
    hbm_bytes: float    # B, HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


class UnknownDevice(LookupError):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peak for device_kind {device_kind!r}; add it to "
            f"bench/peaks.py with its source") from None
