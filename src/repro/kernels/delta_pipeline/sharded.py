"""Sharded one-HBM-pass round: ``delta_pipeline`` under mesh rules.

``delta_pipeline_apply_sharded`` wraps the fused pipeline in a
``shard_map`` over the client-sharded (C, P) delta buffer. Each shard
runs the full per-client half locally — clip norms (every client's
(P,) row lives on exactly one shard, so the norms are exact), the
compression table, and the UNnormalized Eq. 6 partial weighted sum via
the ``delta_pipeline_partial`` Pallas kernel. The partial (P,) sums and
the Σdm / Σm weight totals are packed into ONE (P+2,) vector and
combined with a single ``psum`` over the client mesh axes — preserving
the repo's one-inter-client-all-reduce-per-round HLO contract
(``dist/hlo_analysis.analyze_hlo``). The normalize → DP noise →
momentum → apply epilogue runs replicated after the psum, mirroring the
unsharded kernel's formulas term for term.

Fog tier (``fog_nodes > 1``): the FedFog edge → fog → cloud reduction
maps onto the mesh by carving the client axes into a LEADING fog prefix
and an edge suffix — ``fog_nodes`` must equal the product of a leading
prefix of ``client_axes`` (in the multi-pod plans ``("pod", "client")``,
the fog tier IS the pod axis). The combine then runs as one packed psum
per tier: tier 1 reduces the edge suffix axes (each fog aggregator's
partial), tier 2 reduces the fog prefix axes (the cloud combine).
``fog_nodes=1`` keeps the single flat psum — byte-identical to the
pre-fog kernel. ``dist/hlo_analysis.assert_inter_client_contract``
asserts the per-tier collective counts post-compile.

Numerics: the sharded sum reduces per-shard partials in a different
order than the single-device (1, C)×(C, P) matmul, so the result
matches ``delta_pipeline_apply`` / ``ref.py`` to float tolerance, not
bitwise (tests/test_sharded_pipeline.py pins the tolerance). The DP
noise stream is IDENTICAL across paths: the caller builds the (P,)
noise vector from the same key recipe and it is added post-psum.

Robust aggregators (median / trimmed) need every client's coordinate on
one device to sort — they stay on the single-host kernel path; under
mesh rules they keep the reference path (see the gate matrix in
docs/EXPERIMENTS.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.delta_pipeline.delta_pipeline import (
    _EPS,
    delta_pipeline_apply,
    delta_pipeline_partial,
)


def _norm_axes(client_axes) -> tuple[str, ...]:
    if isinstance(client_axes, str):
        return (client_axes,)
    return tuple(client_axes)


def split_fog_axes(
    mesh: jax.sharding.Mesh, client_axes, fog_nodes: int
) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Split client mesh axes into (fog prefix, edge suffix).

    The fog tier must align with the device topology for the two-psum
    reduction to be a real hierarchy: ``fog_nodes`` has to equal the
    product of a LEADING prefix of the client axes (pod-major layout).
    Returns ``(fog_axes, edge_axes)``; raises when no prefix matches.
    """
    axes = _norm_axes(client_axes)
    prod = 1
    for i in range(len(axes) + 1):
        if prod == fog_nodes:
            return axes[:i], axes[i:]
        if i < len(axes):
            prod *= mesh.shape[axes[i]]
    sizes = tuple(mesh.shape[a] for a in axes)
    raise ValueError(
        f"fog_nodes={fog_nodes} must equal the product of a leading "
        f"prefix of the client mesh axes {axes} (sizes {sizes}); "
        "use a multi_pod plan whose pod axis is the fog tier"
    )


def combine_epilogue(
    agg_sum: jax.Array,  # (P,) combined UNnormalized weighted delta sum
    sdm: jax.Array,  # scalar Σ mask·|D|·staleness-discount
    sm: jax.Array,  # scalar Σ mask·|D|
    base: jax.Array,  # (P,) fused global model
    lr: jax.Array,
    *,
    has_stale: bool,
    dp_noise: jax.Array | None = None,
    momentum: jax.Array | None = None,
    server_optimizer: str = "fedavg",
    server_momentum: float = 0.9,
) -> tuple[jax.Array, jax.Array | None]:
    """Cloud-side epilogue shared by every hierarchical combine.

    Normalize → DP noise → server momentum/Adam → apply, mirroring the
    unsharded ``delta_pipeline_apply`` formulas term for term. Runs
    replicated after the last psum in the sharded kernel, and on the
    summed fog partials in the single-host ``fl.fog.fog_pipeline_apply``
    path. Returns ``(new_base, new_momentum | None)``.
    """
    if has_stale:
        # normalize by Σdm, then the async_aggregate global damping
        agg = agg_sum / (sdm + _EPS)
        agg = agg * ((sdm + _EPS) / (sm + _EPS))
    else:
        agg = agg_sum / (sm + _EPS)
    if dp_noise is not None:
        agg = agg + dp_noise.astype(jnp.float32)
    if momentum is not None:
        mu2 = server_momentum * momentum.astype(jnp.float32) + agg
        if server_optimizer == "fedadam":
            step = lr * mu2 / (jnp.sqrt(jnp.square(agg)) + 1e-3)
        else:  # fedavgm
            step = lr * mu2
        out = (base.astype(jnp.float32) + step).astype(base.dtype)
        return out, mu2.astype(momentum.dtype)
    out = (base.astype(jnp.float32) + lr * agg).astype(base.dtype)
    return out, None


def delta_pipeline_apply_sharded(
    updates: jax.Array,  # (C, P) fused deltas, sharded over client axes
    base: jax.Array,  # (P,) fused global model (replicated)
    mask: jax.Array,  # (C,) participation, sharded like the client axis
    weights: jax.Array,  # (C,) |D_i| dataset sizes
    lr: jax.Array | float = 1.0,
    staleness: jax.Array | None = None,  # (C,)
    staleness_exponent: jax.Array | float = 0.0,
    dp_noise: jax.Array | None = None,  # (P,) replicated, caller-built
    momentum: jax.Array | None = None,  # (P,) fused server momentum
    *,
    mesh: jax.sharding.Mesh,
    client_axes,  # mesh axis name(s) the client dim is sharded over
    fog_nodes: int = 1,
    clip_norm: float = 0.0,
    compression: str = "none",
    topk_fraction: float = 0.05,
    seg_sizes: tuple[int, ...] | None = None,
    server_optimizer: str = "fedavg",
    server_momentum: float = 0.9,
    block_d: int | None = None,
    interpret: bool | None = None,
):
    """Sharded fused delta pipeline: one HBM pass per shard, one psum
    per reduction tier.

    Same gate semantics and return convention as
    ``delta_pipeline_apply`` (fedavg aggregator only). Designed to be
    called under an enclosing jit that holds the mesh context (the
    sharded round fn); it is NOT itself jitted so the ``mesh`` /
    ``client_axes`` objects never need hashing.
    """
    axes = _norm_axes(client_axes)
    ways = 1
    for a in axes:
        ways *= mesh.shape[a]
    if ways <= 1:
        # Degenerate mesh: no client sharding — the single-device kernel
        # IS the sharded kernel with zero cross-shard combines. A fog
        # tier still changes the reduction order, so it routes to the
        # single-host fog loop.
        if fog_nodes > 1:
            from repro.fl.fog import fog_pipeline_apply

            return fog_pipeline_apply(
                updates, base, mask, weights, lr,
                staleness, staleness_exponent, dp_noise, momentum,
                fog_nodes=fog_nodes,
                clip_norm=clip_norm, compression=compression,
                topk_fraction=topk_fraction, seg_sizes=seg_sizes,
                server_optimizer=server_optimizer,
                server_momentum=server_momentum,
                block_d=block_d, interpret=interpret,
            )
        return delta_pipeline_apply(
            updates, base, mask, weights, lr,
            staleness, staleness_exponent, dp_noise, momentum,
            clip_norm=clip_norm, compression=compression,
            topk_fraction=topk_fraction, seg_sizes=seg_sizes,
            server_optimizer=server_optimizer,
            server_momentum=server_momentum,
            block_d=block_d, interpret=interpret,
        )

    fog_axes: tuple[str, ...] = ()
    edge_axes = axes
    if fog_nodes > 1:
        fog_axes, edge_axes = split_fog_axes(mesh, axes, fog_nodes)

    c, d = updates.shape
    if c % ways:
        raise ValueError(f"client count {c} not divisible by mesh ways {ways}")
    has_mu = momentum is not None and server_optimizer in (
        "fedavgm", "fedadam"
    )
    has_dp = dp_noise is not None
    has_stale = staleness is not None
    mu_in = momentum if has_mu else jnp.zeros((), jnp.float32)
    noise_in = dp_noise if has_dp else jnp.zeros((), jnp.float32)
    stale_in = staleness if has_stale else jnp.zeros_like(mask, jnp.float32)
    lr_in = jnp.asarray(lr, jnp.float32)
    sexp_in = jnp.asarray(staleness_exponent, jnp.float32)

    row = P(axes if len(axes) > 1 else axes[0])
    cxp = P(axes if len(axes) > 1 else axes[0], None)
    rep = P()

    def body(upd, base_l, mask_l, w_l, lr_l, stale_l, sexp_l, noise_l, mu_l):
        # -- per-shard half: exact clip + compression + partial sums --- #
        m = mask_l.astype(jnp.float32) * w_l.astype(jnp.float32)
        if has_stale:
            s = jnp.maximum(stale_l.astype(jnp.float32), 0.0)
            dm = m * (1.0 + s) ** (-sexp_l)
        else:
            dm = m
        partial = delta_pipeline_partial(
            upd, dm,
            clip_norm=clip_norm, compression=compression,
            topk_fraction=topk_fraction, seg_sizes=seg_sizes,
            block_d=block_d, interpret=interpret,
        )
        packed = jnp.concatenate(
            [partial, jnp.sum(dm)[None], jnp.sum(m)[None]]
        )
        if fog_nodes > 1:
            # -- hierarchical combine: one packed psum per tier -------- #
            # Tier 1 (edge → fog): reduce the edge suffix axes; after
            # this, `packed` is the fog aggregator's partial, replicated
            # within each fog group. Skipped when each fog holds exactly
            # one shard (its local partial IS the fog partial).
            edge_ways = 1
            for a in edge_axes:
                edge_ways *= mesh.shape[a]
            if edge_ways > 1:
                packed = jax.lax.psum(packed, edge_axes)
            # Tier 2 (fog → cloud): combine the fog partials across the
            # pod-major fog prefix.
            packed = jax.lax.psum(packed, fog_axes)
        else:
            # -- the ONE cross-shard combine: partials + weight totals - #
            packed = jax.lax.psum(packed, axes)
        agg_sum, sdm, sm = packed[:d], packed[d], packed[d + 1]

        # -- replicated epilogue: mirror the unsharded kernel's math --- #
        out, mu2 = combine_epilogue(
            agg_sum, sdm, sm, base_l, lr_l,
            has_stale=has_stale,
            dp_noise=noise_l if has_dp else None,
            momentum=mu_l if has_mu else None,
            server_optimizer=server_optimizer,
            server_momentum=server_momentum,
        )
        if mu2 is None:
            mu2 = jnp.zeros((), jnp.float32)
        return out, mu2

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(cxp, rep, row, row, rep, row, rep, rep, rep),
        out_specs=(rep, rep),
        check_vma=False,
    )
    out, mu2 = mapped(
        updates, base, mask, weights, lr_in, stale_in, sexp_in,
        noise_in, mu_in,
    )
    if has_mu:
        return out, mu2
    return out
