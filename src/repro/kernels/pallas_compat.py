"""Shared interpret-mode policy for the Pallas TPU kernels.

``interpret_default`` is the single definition of the kernel families'
interpret-mode fallback: run the real Mosaic lowering on TPU, the Pallas
interpreter everywhere else (CPU/GPU hosts — a correctness tool, not a
perf path). Kernels take ``interpret: bool | None = None`` and resolve it
through here so the TPU-detection logic cannot drift between families.
"""
from __future__ import annotations

import jax


def interpret_default(interpret: bool | None = None) -> bool:
    """Resolve a kernel's interpret-mode argument: an explicit value wins;
    ``None`` means "interpret everywhere except a real TPU backend"."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


__all__ = ["interpret_default"]
