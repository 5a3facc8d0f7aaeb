"""Pallas TPU kernel: fused masked-weighted FedAvg aggregation + apply.

The aggregation step of Eq. 6 touches every parameter once per client —
it is purely memory-bound. XLA lowers the naive expression as (mask·weight
broadcast) → (N,D) multiply → reduce → add: up to three passes over the
(N, D) update matrix in HBM. This kernel fuses normalization, weighting,
reduction and the server apply into ONE pass with a single (1,N)×(N,bd)
MXU matmul per tile:

  grid = (D / block_d,)
  blocks: updates (N, block_d) VMEM tile, base (block_d,), out (block_d,)
  normalized client weights are tiny (N,) and ride along as a full block.

block_d = 2048 with N = 64 clients is a 512 KB bf16 tile — VMEM-friendly
and wide enough to saturate HBM bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import interpret_default

DEFAULT_BLOCK_D = 2048


def _fedavg_kernel(wn_ref, upd_ref, base_ref, out_ref):
    wn = wn_ref[0, :].astype(jnp.float32)  # (N,) lr-scaled normalized weights
    upd = upd_ref[...].astype(jnp.float32)  # (N, bd)
    agg = jax.lax.dot_general(
        wn[None, :], upd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (1, bd)
    out_ref[...] = (
        base_ref[...].astype(jnp.float32) + agg[0]
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fedavg_apply(
    updates: jax.Array,  # (N, D)
    base: jax.Array,  # (D,)
    mask: jax.Array,  # (N,) bool
    weights: jax.Array,  # (N,) |D_i|
    lr: jax.Array | float = 1.0,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = interpret_default(interpret)
    n, d = updates.shape
    wn = mask.astype(jnp.float32) * weights.astype(jnp.float32)
    # lr rides in the tiny (1, N) weight vector rather than as a kernel
    # compile-time constant, so a traced server_lr (sweep-lifted config
    # data) does not force a recompile per grid point.
    wn = (jnp.asarray(lr, jnp.float32) * wn / (jnp.sum(wn) + 1e-12))[None, :]

    block_d = min(block_d, d)
    pad = (-d) % block_d
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
        base = jnp.pad(base, (0, pad))
    dp = d + pad
    grid = (dp // block_d,)

    out = pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((block_d,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((dp,), base.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="fedavg_apply",
        interpret=interpret,
    )(wn, updates, base)
    return out[:d]


def fedavg_apply_tree(updates_tree, base_tree, mask, weights, lr=1.0):
    """Apply the kernel leaf-wise over parameter pytrees.

    updates_tree leaves: (N, ...) stacked client deltas; base_tree: (...)."""
    def one(upd, base):
        flat_u = upd.reshape(upd.shape[0], -1)
        flat_b = base.reshape(-1)
        return fedavg_apply(flat_u, flat_b, mask, weights, lr=lr).reshape(
            base.shape
        )

    return jax.tree.map(one, updates_tree, base_tree)
