"""The yardstick's arithmetic: published peaks, and the bytes the server
step requires, from its shapes. A model's operations per token are
counted beside its plain reference (``refs/<reference>.py``), so that a
new configuration brings its own count.

Counts are of the algorithm, not of an implementation: recomputation,
padding and layout copies are not counted.
"""
from __future__ import annotations

# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for {kind!r}; known {sorted(PEAKS)}")
    return PEAKS[kind]


def delta_pipeline_bytes(slots: int, param_bytes: int, params: int) -> float:
    """One FedAvgM server step: the (C, P) float32 deltas read once, the
    parameters (their stored dtypes) and the float32 momentum each read
    and written once."""
    return 4.0 * slots * params + 2.0 * param_bytes + 8.0 * params
