"""Production mesh contract and the per-chip peak table.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import dataclasses

import jax

CHIPS_PER_POD = 256


def make_production_mesh(*, multi_pod: bool = False):
    """The 256-chip (or 2×256) pod mesh, every axis Auto."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_link_bw: float  # bytes/s per inter-chip link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth, 1,600 Gbit/s of
# inter-chip interconnect over 4 links (50 GB/s each).
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}

# The chip of the production pods the dry-run projects onto.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind without published peaks raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
