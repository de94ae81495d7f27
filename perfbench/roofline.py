"""Chip peaks and the operation and byte counts of the decision-path kernels.

Peaks of one chip, keyed by JAX's ``device_kind``.  Source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s).  No float32 peak is published for the v5e, so the compute roof
of a float32 kernel is the bf16 one.  A device that is not in the table is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

F32 = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def mlp_flops(rows: int, widths: list[int]) -> float:
    """Multiply-add operations (2 per MAC) plus bias adds of a dense MLP
    over ``rows`` rows with layer widths ``[in, h1, ..., out]``."""
    per_row = sum(2 * a * b + b for a, b in zip(widths[:-1], widths[1:]))
    return float(rows * per_row)


def mlp_bytes(rows: int, widths: list[int], calls: int = 1,
              mask: bool = True) -> float:
    """Bytes fused float32 MLP kernel calls must move over ``rows`` rows
    in all: the input rows, the weights and biases once per call, the
    output rows and, for the policy kernel, the queue mask."""
    weights = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    per_row = widths[0] + widths[-1] + (1 if mask else 0)
    return float(F32 * (rows * per_row + calls * weights))


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    """Least time the chip could take: the larger of compute and memory."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["bytes_per_s"])
