"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first
device init, and tests must see a 1-device world.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """jax.make_mesh with Auto axes: the models place activations with
    sharding constraints, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod ('data' x 'model'); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small explicit mesh for tests on host platform devices."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_serving_mesh(n_devices: int = 0):
    """1-D mesh over the first n devices (0 = all) on the 'model' axis —
    the axis the sharding rules map kv_pages onto, so handing this to
    ServingEngine(mesh=...) shards the paged KV pool n_devices ways."""
    import numpy as np
    devs = jax.devices()
    n = n_devices or len(devs)
    return jax.sharding.Mesh(np.array(devs[:n]), ("model",))


# Published per-chip peaks, keyed by `jax.Device.device_kind`.  A device
# kind missing here has no peaks: `peaks` raises rather than assume one.
# "TPU v5 lite" is the TPU v5e — Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s (200 GB/s) of inter-chip
# interconnect per chip over its four 2-D torus links.
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 50e9,             # B/s per link (200 GB/s over 4 links)
    },
}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind` (see PEAKS)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]
