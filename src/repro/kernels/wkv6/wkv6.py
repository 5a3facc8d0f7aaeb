"""Pallas TPU kernel for the RWKV6 (Finch) recurrence — chunked-parallel form.

TPU adaptation (DESIGN.md §6): instead of a step-by-step recurrence (VPU
serial, no MXU work), each time chunk of length C is processed in closed
form with three MXU matmuls:

    P_t   = Π_{s≤t} w_s                          (in-chunk cumulative decay)
    R~    = r ⊙ P_prev      K~ = k / P           (decay-adjusted views)
    inter = R~ @ S                               (contribution of carry-in)
    intra = tril_strict(R~ @ K~ᵀ + diag(r·(u⊙k))) @ V
    S'    = diag(P_C) S + diag(P_C) (K~ᵀ @ V)    (carry-out)

Grid = (B, H, num_chunks), chunk dim "arbitrary": the (K, V) state lives in
VMEM scratch across chunk steps. Default C=32 with fp32 math keeps the
in-chunk decay ratios P_C/P_s well-conditioned (w = exp(-exp(·)) < 1; see
module comment on stability in ops.py).

Blocks: r/k/v/w tiles (1, C, 1, K) stream through VMEM; scratch state
(K, V) fp32 = 16 KB/head at K=V=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, state,
                 *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0, :, 0, :].astype(jnp.float32)  # (C, K)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)  # (C, V)
    w = w_ref[0, :, 0, :].astype(jnp.float32)  # (C, K), in (0, 1)
    u = u_ref[0, :].astype(jnp.float32)  # (K,)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    logp = jnp.cumsum(logw, axis=0)  # (C, K): log Π_{s<=t}
    p = jnp.exp(logp)
    p_prev = jnp.exp(logp - logw)  # Π_{s<t} (exclusive)
    p_last = jnp.exp(logp[-1:])  # (1, K)

    s = state[...]  # (K, V) carry-in
    r_adj = r * p_prev  # (C, K)
    k_adj = k * jnp.exp(-logp)  # k / P

    inter = jax.lax.dot_general(
        r_adj, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, V)
    scores = jax.lax.dot_general(
        r_adj, k_adj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C): entry (t, s) = r_t·(P_{t-1}/P_s ⊙ k_s)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(s_idx < t_idx, scores, 0.0)  # strictly causal
    diag = jnp.sum(r * u[None, :] * k, axis=-1)  # (C,) current-token bonus
    intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + diag[:, None] * v

    y_ref[0, :, 0, :] = (inter + intra).astype(y_ref.dtype)

    ktv = jax.lax.dot_general(
        k_adj, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (K, V)
    state[...] = p_last.T * (s + ktv)

    @pl.when(ci == nc - 1)
    def _flush():
        s_out_ref[0, 0] = state[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def wkv6_fwd(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,
    v: jax.Array,  # (B, T, H, V)
    w: jax.Array,  # (B, T, H, K) decays in (0,1)
    u: jax.Array,  # (H, K)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
):
    """Returns (y (B,T,H,V), final_state (B,H,K,V) fp32)."""
    b, t, h, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    grid = (b, h, nc)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    seq_spec = lambda: pl.BlockSpec(
        (1, chunk, 1, dk), lambda bb, hh, cc: (bb, cc, hh, 0)
    )
    y, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec(),
            seq_spec(),
            pl.BlockSpec((1, chunk, 1, dv), lambda bb, hh, cc: (bb, cc, hh, 0)),
            seq_spec(),
            pl.BlockSpec((1, dk), lambda bb, hh, cc: (hh, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, dv), lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h, dv), r.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="wkv6",
        interpret=interpret,
    )(r, k, v, w, u)
    return y, s_final
