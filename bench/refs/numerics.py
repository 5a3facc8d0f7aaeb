"""Arithmetic the plain references share: float32 matrix products at
HIGHEST precision, and the control's per-tensor float8 (e4m3) rounding of
every product's operands."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8(x):
    """``x`` rounded to float8 e4m3 with one scale for the tensor, as an
    fp8 matrix unit would read it; the gradient passes straight through,
    so only the forward products lose precision."""
    s = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


class Numerics:
    """``quant=None``: float32 at HIGHEST; ``"fp8"``: the control."""

    def __init__(self, quant=None):
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.q = fp8 if quant == "fp8" else (lambda x: x)

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)
