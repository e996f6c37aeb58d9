"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests must keep seeing 1 device.

Target hardware: TPU v5e pods — 16x16 = 256 chips per pod; 2 pods = 512.
Axes: (data, model) single-pod; (pod, data, model) multi-pod.  The FedAR
cohort axis is the data axis (x pod).
"""
from __future__ import annotations

from typing import NamedTuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over actually-available devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"))


class ChipPeaks(NamedTuple):
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link
    source: str


# Keyed by ``jax.Device.device_kind``.  A kind missing here is an error,
# never a default: a roofline against the wrong chip is a wrong number.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s of chip-to-chip interconnect, 4 links
        source='Google Cloud documentation, "TPU v5e"',
    ),
}

# the chip the production meshes above are sized for
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind`` (as ``jax.devices()[0].device_kind``
    reports it); an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None
