"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float      # dense FLOP/s the MXU reaches (bf16 passes)
    hbm_bytes: float  # HBM bytes/s
    source: str


_V5E = Peaks(
    flops=197e12,
    hbm_bytes=819e9,
    source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect")

#: The f32 contractions of today's kernels run as one bf16 pass on the MXU,
#: so the bf16 figure is their ceiling.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": _V5E,      # what JAX reports for a v5e chip
}


class UnknownDevice(KeyError):
    """No published peaks for this ``device_kind``."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
