"""Published peaks per chip, keyed by JAX's ``device_kind``.

Every share the benchmark reports is taken against the bf16 peak,
whatever precision the operands have, so no share can pass 100%.  A
device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s",
    },
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
