"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline borrowed from another chip would be silently wrong.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s. The configurations' float32
GEMMs run at the TPU's default precision, one bfloat16 pass per product, so
the bf16 peak is their compute ceiling.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peaks:
    flops: float       #: FLOP/s of one chip at the precision the GEMMs run in
    hbm_bw: float      #: HBM bytes/s of one chip


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
