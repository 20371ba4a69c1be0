"""Peaks of the chip and the bytes a kernel launch must move.

A kernel's roofline share is the least time the chip could take for the
launch over the time the trace measured.  The kernels here are integer
compares on the vector units: the MXU's published FLOP/s do not bound
them, so the share is the memory term alone, bytes over HBM bandwidth.
The bytes are what the algorithm must move, computed from the launch's
operand shapes: each input read once, each output written once.
"""
from __future__ import annotations

from typing import Dict

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.  "TPU v5
#: lite" is the TPU v5e -- Google Cloud documentation, "TPU v5e": 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "peak_int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def clockscan_bytes(n_cols: int, rows: int, queries: int) -> int:
    """One ClockScan launch over ``rows`` rows (as launched, padded) of
    ``n_cols`` int32 predicated columns against ``queries`` query slots:
    read the columns, the int32 validity and the lo/hi bounds, write one
    32-bit word per 32 queries per row."""
    return 4 * (n_cols * rows + rows + 2 * n_cols * queries
                + rows * (queries // 32))


def roofline_share(n_bytes: float, seconds: float, device_kind: str) -> float:
    """Percent of the HBM roofline: least time over measured time."""
    return 100.0 * (n_bytes / peaks(device_kind)["hbm_bw"]) / seconds
