"""Pallas TPU paged flash-decode attention kernel (ragged slot batch).

One query token per slot against a physical page pool, with the slot ->
page indirection resolved *in the HBM pass*:

  * grid = (S, Hkv, pages_per_slot) — pages innermost ("arbitrary"
    semantics) so the online-softmax state for one (slot, kv-head) lives
    in VMEM scratch across page steps and is flushed exactly once.
  * the page gather rides the k/v BlockSpec index_map through scalar
    prefetch (``pltpu.PrefetchScalarGridSpec``): block ``p`` of slot
    ``s`` is fetched from physical page ``page_table[s, p]`` — no
    gathered copy of the cache is ever materialized in HBM.
  * raggedness is handled in-kernel: ``lengths[s]`` (prefetched to SMEM)
    masks the boundary page and *skips* fully-dead pages (beyond the
    slot's length, outside its sliding window, or an empty slot), so a
    freshly-admitted short request costs only its own pages while a
    long-lived slot in the same batch streams all of its pages.
  * GQA via the q reshape (S, Hkv, groups, hd): each grid step scores
    one kv head's ``groups`` query heads against one page — the kv page
    is read once per kv head, never repeated.
  * the pool is laid out ``(P, Hkv, page, hd)`` so one block is a whole
    ``(page, hd)`` tile of one kv head: the TPU compiler tiles the last
    two block dims, and a single-head slice of a ``(page, Hkv, hd)`` page
    (second-minor dim 1 of Hkv) is refused for head counts such as
    hymba's 5.

Validated bitwise-adjacent (fp32 tolerance: online softmax reassociates)
against ``ref.paged_attention_ref`` in interpret mode across archetypes
(GQA/MHA, sliding window, ragged lengths) in tests/test_serving.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(
    tab_ref,  # (S, n_pages) int32 SMEM — scalar-prefetched page table
    len_ref,  # (S,) int32 SMEM — valid tokens per slot (incl. current)
    q_ref,  # (1, 1, g, hd) VMEM
    k_ref,  # (1, 1, page, hd) VMEM — physical page tab[s, p], one kv head
    v_ref,  # (1, 1, page, hd) VMEM
    o_ref,  # (1, 1, g, hd) VMEM
    m_scratch,  # (g, 128) f32 — running max, lane-broadcast
    l_scratch,  # (g, 128) f32 — running denominator
    acc_scratch,  # (g, hd) f32 — output accumulator
    *,
    scale: float,
    page: int,
    window: int,  # kernel convention: 0 = unbounded causal
):
    s = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    length = len_ref[s]
    q_pos = length - 1  # the query token sits at the slot's last position
    first_k = p * page
    live = first_k < length
    if window > 0:
        # Pages entirely below the sliding window are dead too.
        live = jnp.logical_and(live, (first_k + page - 1) > q_pos - window)

    @pl.when(live)
    def _compute():
        g = q_ref.shape[2]
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (page, hd)
        # HIGHEST keeps both dots in f32 on the MXU (a default-precision
        # f32 dot may round its operands to bf16); decode is bound by the
        # page reads, not by these (g, page) products.
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (g, page)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_scratch[...]
        l_prev = l_scratch[...]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)  # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        probs = jnp.exp(scores - m_safe[:, :1])  # (g, page)
        corr = jnp.exp(
            jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF, m_prev) - m_safe
        )
        l_new = l_prev * corr + jnp.broadcast_to(
            jnp.sum(probs, axis=-1, keepdims=True), l_prev.shape
        )
        v = v_ref[0, 0].astype(jnp.float32)  # (page, hd)
        pv = jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (g, hd)
        acc_scratch[...] = acc_scratch[...] * corr[:, :1] + pv
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(p == n_pages - 1)
    def _flush():
        l = l_scratch[...][:, :1]
        o_ref[0, 0, ...] = (
            acc_scratch[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret")
)
def paged_attention_fwd(
    q: jax.Array,  # (S, Hkv, g, hd) — query heads grouped under kv heads
    k_pages: jax.Array,  # (P, Hkv, page, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (S, pages_per_slot) int32
    lengths: jax.Array,  # (S,) int32
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    s, hkv, g, hd = q.shape
    _, _, page, _ = k_pages.shape
    n_pages = page_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda ss, hh, pp, tab, ln: (ss, hh, 0, 0)),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda ss, hh, pp, tab, ln: (tab[ss, pp], hh, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda ss, hh, pp, tab, ln: (tab[ss, pp], hh, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, hd), lambda ss, hh, pp, tab, ln: (ss, hh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=hd**-0.5, page=page, window=window
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="paged_attention",
        interpret=interpret,
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        q,
        k_pages,
        v_pages,
    )
