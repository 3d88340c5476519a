"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod (16, 16) over ("data", "model") — 256 chips,
one TPU v5e pod — or multi-pod (2, 16, 16) over ("pod", "data", "model") —
512 chips, where the "pod" axis is the DCN-connected outer data axis.
"""
from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh over however many (possibly fake) devices exist — used by
    CI-scale dry-run smoke tests."""
    n = 1
    for s in shape:
        n *= s
    assert len(jax.devices()) >= n, (len(jax.devices()), shape)
    return compat.make_mesh(shape, axes)


# Per-chip peaks, keyed by ``jax.Device.device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16, 393
# TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s of interconnect (four
# 50 GB/s links; the roofline charges one). A kind not in the table is an
# error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "int8_ops": 393e12,        # OP/s
        "hbm_bw": 819e9,           # bytes/s
        "hbm_bytes": 16 * 1024**3,  # capacity per chip
        "ici_bw": 50e9,            # bytes/s per link
    },
}
# the chip the production meshes above are built from (a v5e pod)
PRODUCTION_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
