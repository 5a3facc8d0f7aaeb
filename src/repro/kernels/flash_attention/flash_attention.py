"""Pallas TPU flash-attention forward kernel (causal / SWA / GQA).

Design (TPU-native, not a CUDA port):
  * grid = (B, H, num_q_blocks, num_kv_blocks) — kv innermost ("arbitrary"
    semantics), so the online-softmax state for one q tile lives in VMEM
    scratch across kv steps and is flushed to HBM exactly once per q tile.
  * BlockSpec tiles: q (1,1,block_q,hd), k/v (1,1,block_kv,hd) — for the
    default (block_q, block_kv, hd) = (256, 512, 128) that is a
    ~(256+2·512)·128·2B ≈ 0.3 MB streaming working set plus (256×128) fp32
    accumulators, comfortably inside the ~16 MB/core VMEM budget, with the
    MXU-aligned 128-lane last dim.
  * GQA via the k/v index_map (head h reads kv head h // group) — no
    repeated-KV materialization in HBM.
  * causal + sliding-window handled by *block skipping* (out-of-mask tiles
    are never visited: the kv grid dimension is bounded per q tile) plus an
    in-tile mask on the boundary tiles.

Validated against ref.py in interpret mode (tests/test_kernels.py sweeps
shapes/dtypes); on real TPUs drop-in via ops.flash_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 512
_NEG_INF = -1e30


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch,
    *, scale: float, block_q: int, block_kv: int, sq: int, sk: int,
    window: int, bidirectional: bool,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # Positions: q rows sit at the tail of the key timeline.
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + (sk - sq)
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        if not bidirectional:
            mask = k_pos <= q_pos
            if window > 0:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scratch[...]  # (bq, 128) lane-broadcast stats
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, :1])  # (bq, bkv)
        corr = jnp.exp(
            jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF, m_prev) - m_safe
        )
        l_new = l_prev * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape
        )
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, hd)
        acc_scratch[...] = acc_scratch[...] * corr[:, :1] + pv
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    if bidirectional:
        compute()
    else:
        # Block-level skip: tile is dead if entirely above the diagonal or
        # entirely outside the sliding window.
        first_q = qi * block_q + (sk - sq)
        last_q = first_q + block_q - 1
        first_k = kj * block_kv
        dead_causal = first_k > last_q
        dead_window = (
            (first_q - (first_k + block_kv - 1)) >= window if window > 0 else False
        )
        pl.when(jnp.logical_not(jnp.logical_or(dead_causal, dead_window)))(compute)

    @pl.when(kj == nk - 1)
    def _flush():
        l = l_scratch[...][:, :1]
        o_ref[0, 0, ...] = (
            acc_scratch[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "window", "bidirectional", "block_q", "block_kv", "interpret"
    ),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, Hkv, Sk, hd)
    v: jax.Array,
    *,
    window: int = 0,
    bidirectional: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    groups = h // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0, (sq, block_q, sk, block_kv)
    nq, nk = sq // block_q, sk // block_kv

    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _fwd_kernel,
        scale=hd**-0.5,
        block_q=block_q,
        block_kv=block_kv,
        sq=sq,
        sk=sk,
        window=window,
        bidirectional=bidirectional,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, hd),
                lambda bb, hh, qq, kk, g=groups: (bb, hh // g, kk, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, hd),
                lambda bb, hh, qq, kk, g=groups: (bb, hh // g, kk, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda bb, hh, qq, kk: (bb, hh, qq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
