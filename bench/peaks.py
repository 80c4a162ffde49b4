"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

A device that is not in the table is an error, never a default: a share
of an unknown peak is no measurement.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float            # FLOP/s, dense bf16 matmul
    ops_int8: float              # OP/s, int8 matmul
    hbm_bytes_s: float           # bytes/s
    hbm_bytes: int               # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 393e12, 819e9, 16 * 10 ** 9,
                         'Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def floor_time_s(flops: float, nbytes: float, peaks: Peaks) -> float:
    """Least time the chip could take for the work: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks.flops_bf16, nbytes / peaks.hbm_bytes_s)
