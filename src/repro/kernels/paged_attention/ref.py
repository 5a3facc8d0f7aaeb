"""Dense-gather reference for paged decode attention.

The oracle gathers each slot's pages back into a contiguous
``(S, max_len, Hkv, hd)`` cache and calls the exact decode-attention the
static serving path uses (``models.layers.attention_decode``) — so the
paged kernel is tested against the SAME attention the sequential
per-request oracle runs, keeping the serving engine's token-for-token
contract and the kernel's oracle discipline one and the same check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import attention_decode

Array = jax.Array


def gather_pages(pages: Array, page_table: Array) -> Array:
    """(P, Hkv, page, hd) pool + (S, n) table -> contiguous (S, n*page, Hkv, hd).

    Logical pages are gathered in table order, so position ``t`` of slot
    ``s`` lands at row ``t`` — identical layout to a contiguous KV cache.
    """
    s, n = page_table.shape
    _, hkv, page, hd = pages.shape
    g = jnp.swapaxes(pages[page_table], 2, 3)  # (S, n, page, Hkv, hd)
    return g.reshape(s, n * page, hkv, hd)


def paged_attention_ref(
    q: Array,  # (S, H, hd) — one query token per slot
    k_pages: Array,  # (P, Hkv, page, hd) physical page pool
    v_pages: Array,  # (P, Hkv, page, hd)
    page_table: Array,  # (S, pages_per_slot) int32 — logical -> physical
    lengths: Array,  # (S,) int32 — valid tokens per slot INCLUDING current
    window: int = -1,  # model convention: -1/GLOBAL = unbounded causal
) -> Array:
    """Ragged decode attention over the paged cache, dense-gather form.

    Slots with ``lengths == 0`` (empty/evicted) return exact zeros.
    """
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    out = attention_decode(q[:, None], k, v, lengths - 1, window)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(q.dtype)
