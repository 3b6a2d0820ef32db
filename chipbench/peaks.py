"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

"TPU v5 lite" is the TPU v5e (Google Cloud documentation, "TPU v5e"):
197 TFLOP/s in bfloat16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect.  A device kind missing here is an error: no
share of a peak is ever computed against a guessed one.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,   # FLOP/s
        "hbm_bytes": 819e9,     # B/s
        "hbm_capacity": 16e9,   # B
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (have {sorted(PEAKS)})")
    return PEAKS[device_kind]
