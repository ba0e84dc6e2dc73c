"""The one table of device peaks the benchmark divides by.

Keyed by ``jax.devices()[0].device_kind``. A kind that is not here is an
error, never a default: a roofline share against a guessed peak is not a
measurement. A later PR adds a row; it does not edit one.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
    # 819 GB/s of HBM bandwidth, 16 GB of HBM a chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no row of benchmark/peaks.py for device kind %r; add one "
            "with its source" % (device_kind,)) from None


def least_seconds(flops, bytes_moved, peaks):
    """Roofline: the least time the chip could take for this much
    arithmetic and this much traffic, and which of the two bounds it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = bytes_moved / peaks["hbm_bytes_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "bandwidth"
