"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A device that is not listed is an
error, never a default: a roofline share against the wrong peak is a wrong
number that looks right.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip.  JAX names the chip
    # "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py"
                       ) from None
