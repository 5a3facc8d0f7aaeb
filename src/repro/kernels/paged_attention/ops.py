"""jit'd public wrapper for the paged flash-decode attention kernel.

Accepts the serving engine's layout — q ``(S, H, hd)`` (one query token
per slot), the physical page pool and the slot page table — with the
model-layer window convention (``-1``/GLOBAL = unbounded causal). Falls
back to interpret mode off-TPU via the shared ``pallas_compat`` policy so
the same call-site runs everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import paged_attention_fwd
from repro.kernels.pallas_compat import interpret_default


def paged_attention(
    q: jax.Array,  # (S, H, hd)
    k_pages: jax.Array,  # (P, Hkv, page, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (S, pages_per_slot) int32
    lengths: jax.Array,  # (S,) int32 — valid tokens per slot incl. current
    window=-1,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    s, h, hd = q.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    assert g * hkv == h, (h, hkv)
    win = int(window) if window is not None else -1
    win = 0 if win < 0 else win  # kernel convention: 0 = global
    out = paged_attention_fwd(
        q.reshape(s, hkv, g, hd),
        k_pages,
        v_pages,
        page_table,
        lengths,
        window=win,
        interpret=interpret_default(interpret),
    )
    out = out.reshape(s, h, hd)
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(q.dtype)
