"""Fog-tier hierarchical reduction — edge → fog → cloud (paper Fig. 1).

The FedFog topology is edge devices → fog nodes → cloud, but Eq. 6 is
associative: the staleness-discounted weighted aggregate decomposes into
per-fog PARTIAL sums (each fog aggregator reduces only its own clients)
plus one tiny cloud combine of ``fog_nodes`` partials:

    partial_f = Σ_{i∈f} m_i·disc_i·Δ_i        (P,) per fog
    Σdm_f     = Σ_{i∈f} m_i·disc_i            scalar per fog
    Σm_f      = Σ_{i∈f} m_i                   scalar per fog
    cloud:  agg = (Σ_f partial_f) / (Σ_f Σdm_f + ε) · damping

which equals the flat aggregate up to float reassociation (the partial
sums reduce in per-fog order). Robust aggregators (median / trimmed) are
order statistics over the FULL client axis — they do not decompose into
fog partials, so ``fog_nodes > 1`` composes only with ``fedavg`` (the
callers raise ``ValueError`` otherwise).

Three entries share the cloud-combine math:

  * :func:`fog_aggregate` — reference path: ``segment_sum`` partials over
    an arbitrary client→fog assignment (the hypothesis property in
    tests/test_fog_population.py permutes it), matching
    ``core.aggregation.fedavg_stacked`` / ``sim.events.staleness
    .async_aggregate`` to float tolerance.
  * :func:`fog_pipeline_apply` — kernel path: one
    ``kernels.delta_pipeline.delta_pipeline_partial`` Pallas pass per
    fog's contiguous client block, then the shared replicated epilogue
    (``kernels.delta_pipeline.sharded.combine_epilogue``).
  * under mesh ``rules`` the fog tier maps onto the pod×client axes:
    ``delta_pipeline_apply_sharded(..., fog_nodes=F)`` runs ONE packed
    psum per tier (dist/hlo_analysis asserts the per-tier contract).

This module also hosts the population/cohort sampling used by both
engines: a population of ``M`` virtual clients is carried as cheap (M,)
scheduler/telemetry rows, and each round gathers a C-sized cohort so all
O(model) work (local updates, the fused (C, P) buffer, the Pallas pass)
is built for C clients only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import PopulationSchedulerState, SchedulerState

Array = jax.Array
_EPS = 1e-12  # matches core.aggregation / kernels.delta_pipeline


# --------------------------------------------------------------------- #
# population / cohort sampling
# --------------------------------------------------------------------- #
def stratified_cohort(key: Array, population: int, cohort: int) -> Array:
    """Sample ``cohort`` distinct client ids from ``[0, population)``.

    Stratified without-replacement draw in O(cohort): stratum ``i`` is
    ``[⌊i·M/C⌋, ⌊(i+1)·M/C⌋)`` and contributes exactly one uniform id,
    so the ids come back sorted and distinct by construction — the
    gather/scatter rows of the population state never collide within a
    round. With ``population == cohort`` every stratum has width 1 and
    the sample is ``arange(cohort)`` (the dense registry).
    """
    bounds = (jnp.arange(cohort + 1, dtype=jnp.int32) * population) // cohort
    lo, hi = bounds[:-1], bounds[1:]
    return lo + jax.random.randint(
        key, (cohort,), jnp.zeros_like(lo), jnp.maximum(hi - lo, 1)
    )


def gather_rows(tree, ids: Array):
    """Row-gather every leaf of a per-client pytree (leading dim = N)."""
    return jax.tree.map(lambda a: a[ids], tree)


def scatter_rows(tree, ids: Array, rows):
    """Scatter cohort rows back into the per-population pytree."""
    return jax.tree.map(lambda a, r: a.at[ids].set(r), tree, rows)


def gather_cohort_sched(
    pop: PopulationSchedulerState, ids: Array, hist_fn
) -> SchedulerState:
    """Materialize a cohort-sized ``SchedulerState`` from population rows.

    ``prev_hist`` is NOT stored per population client ((M, V) floats is
    the one piece of scheduler state that is not cheap at 1M clients).
    Instead the population state carries ``last_hist_round`` and the
    drift reference is recomputed for the C cohort members only:
    ``hist_fn(ids, round)`` is deterministic in (client, round), so the
    recomputed reference equals what ``schedule_round`` would have
    stored (``drift_score`` renormalizes both sides, so the smoothing
    double-application is value-neutral for the gate).
    """
    from repro.core.drift import normalize_histogram

    prev = normalize_histogram(hist_fn(ids, pop.last_hist_round[ids]))
    return SchedulerState(
        prev_hist=prev,
        theta_e=pop.theta_e[ids],
        warm=pop.warm[ids],
        last_used=pop.last_used[ids],
        energy_spent=pop.energy_spent[ids],
        round_index=pop.round_index,
    )


def scatter_cohort_sched(
    pop: PopulationSchedulerState,
    ids: Array,
    cohort: SchedulerState,
    hist_round: Array,
) -> PopulationSchedulerState:
    """Write a cohort's advanced scheduler rows back into the population.

    ``prev_hist`` is dropped in favour of recording which round the
    cohort's histograms were taken at (``last_hist_round``); everything
    else scatters row-for-row. Unsampled clients keep their rows frozen
    until the next time the cohort lands on them.
    """
    return PopulationSchedulerState(
        theta_e=pop.theta_e.at[ids].set(cohort.theta_e),
        warm=pop.warm.at[ids].set(cohort.warm),
        last_used=pop.last_used.at[ids].set(cohort.last_used),
        energy_spent=pop.energy_spent.at[ids].set(cohort.energy_spent),
        last_hist_round=pop.last_hist_round.at[ids].set(
            jnp.asarray(hist_round, jnp.int32)
        ),
        round_index=cohort.round_index,
    )


def gather_sched_rows(sched: SchedulerState, ids: Array) -> SchedulerState:
    """Cohort rows of a FULL (population-sized) ``SchedulerState`` —
    the pod-scale runtime variant, where the drift histograms are opaque
    caller data and ``prev_hist`` stays materialized at (M, V)."""
    return SchedulerState(
        prev_hist=sched.prev_hist[ids],
        theta_e=sched.theta_e[ids],
        warm=sched.warm[ids],
        last_used=sched.last_used[ids],
        energy_spent=sched.energy_spent[ids],
        round_index=sched.round_index,
    )


def scatter_sched_rows(
    pop: SchedulerState, ids: Array, rows: SchedulerState
) -> SchedulerState:
    return SchedulerState(
        prev_hist=pop.prev_hist.at[ids].set(rows.prev_hist),
        theta_e=pop.theta_e.at[ids].set(rows.theta_e),
        warm=pop.warm.at[ids].set(rows.warm),
        last_used=pop.last_used.at[ids].set(rows.last_used),
        energy_spent=pop.energy_spent.at[ids].set(rows.energy_spent),
        round_index=rows.round_index,
    )


# --------------------------------------------------------------------- #
# fog-tier reduction
# --------------------------------------------------------------------- #
def fog_assignment(num_clients: int, fog_nodes: int) -> Array:
    """Default client→fog map: contiguous blocks (fog ``f`` owns clients
    ``[f·C/F, (f+1)·C/F)``) — the layout the kernel path's per-fog
    reshape and the pod-major mesh sharding both assume."""
    return (
        jnp.arange(num_clients, dtype=jnp.int32) * fog_nodes
    ) // num_clients


def fog_partial_sums(
    updates: Array,  # (C, P) fused client deltas
    mask: Array,  # (C,) participation
    weights: Array,  # (C,) |D_i| dataset sizes
    fog_nodes: int,
    staleness: Array | None = None,  # (C,)
    staleness_exponent: Array | float = 0.0,
    assignment: Array | None = None,  # (C,) int32 fog id per client
):
    """Per-fog partial sums: ``(partials (F, P), sdm (F,), sm (F,))``.

    This is the fog aggregator's whole job — each fog reduces only its
    own clients' rows; nothing model-sized crosses fogs until the cloud
    combine. ``assignment`` defaults to contiguous blocks.
    """
    if assignment is None:
        assignment = fog_assignment(updates.shape[0], fog_nodes)
    m = mask.astype(jnp.float32) * weights.astype(jnp.float32)
    if staleness is not None:
        s = jnp.maximum(jnp.asarray(staleness, jnp.float32), 0.0)
        dm = m * (1.0 + s) ** (-jnp.asarray(staleness_exponent, jnp.float32))
    else:
        dm = m
    partials = jax.ops.segment_sum(
        dm[:, None] * updates.astype(jnp.float32), assignment,
        num_segments=fog_nodes,
    )
    sdm = jax.ops.segment_sum(dm, assignment, num_segments=fog_nodes)
    sm = jax.ops.segment_sum(m, assignment, num_segments=fog_nodes)
    return partials, sdm, sm


def cloud_combine(
    partials: Array,  # (F, P) fog partial weighted sums
    sdm: Array,  # (F,) per-fog Σ mask·|D|·disc
    sm: Array,  # (F,) per-fog Σ mask·|D|
    has_stale: bool,
) -> Array:
    """Cloud tier: combine fog partials into the normalized aggregate.

    Mirrors the sharded kernel's post-psum normalization term for term
    (Σpartial/(Σdm+ε) then the ``async_aggregate`` global damping when
    staleness weighting is on).
    """
    agg_sum = jnp.sum(partials, axis=0)
    tdm, tm = jnp.sum(sdm), jnp.sum(sm)
    if has_stale:
        agg = agg_sum / (tdm + _EPS)
        return agg * ((tdm + _EPS) / (tm + _EPS))
    return agg_sum / (tm + _EPS)


def fog_aggregate(
    updates: Array,  # (C, P) fused client deltas
    mask: Array,
    weights: Array,
    fog_nodes: int,
    staleness: Array | None = None,
    staleness_exponent: Array | float = 0.0,
    assignment: Array | None = None,
) -> Array:
    """Hierarchical Eq. 6: fog partials → cloud combine, on one host.

    Equals ``fedavg_stacked`` (no staleness) / ``async_aggregate``
    (staleness) up to float reassociation, for ANY client→fog
    assignment — associativity is the whole correctness argument, and
    the hypothesis property in tests/test_fog_population.py exercises it
    under permuted assignments.
    """
    partials, sdm, sm = fog_partial_sums(
        updates, mask, weights, fog_nodes, staleness, staleness_exponent,
        assignment,
    )
    return cloud_combine(partials, sdm, sm, staleness is not None)


def fog_aggregate_tree(
    deltas,  # (C, ...)-stacked pytree of client deltas
    mask: Array,
    weights: Array,
    fog_nodes: int,
    staleness: Array | None = None,
    staleness_exponent: Array | float = 0.0,
):
    """Pytree wrapper for the reference engines: fuse → fog_aggregate →
    unfuse, so the stacked-delta paths route through the identical
    hierarchical math as the fused-buffer paths."""
    from repro.fl.fuse import fuse_clients

    cat, unfuse = fuse_clients(deltas)
    return unfuse(
        fog_aggregate(
            cat, mask, weights, fog_nodes, staleness, staleness_exponent
        )
    )


def fog_pipeline_apply(
    updates: Array,  # (C, P) fused client deltas
    base: Array,  # (P,) fused global model
    mask: Array,
    weights: Array,
    lr: Array | float = 1.0,
    staleness: Array | None = None,
    staleness_exponent: Array | float = 0.0,
    dp_noise: Array | None = None,  # (P,) caller-built
    momentum: Array | None = None,  # (P,) fused server momentum
    *,
    fog_nodes: int,
    clip_norm: float = 0.0,
    compression: str = "none",
    topk_fraction: float = 0.05,
    seg_sizes: tuple[int, ...] | None = None,
    server_optimizer: str = "fedavg",
    server_momentum: float = 0.9,
    block_d: int | None = None,
    interpret: bool | None = None,
):
    """Single-host kernel path of the fog tier (fedavg only).

    Each fog's contiguous (C/F, P) client block runs ONE
    ``delta_pipeline_partial`` Pallas pass (clip norms and compression
    tables are fog-local, like the sharded kernel's shard-local ones);
    the cloud combines the F partials and runs the shared replicated
    epilogue. Same return convention as ``delta_pipeline_apply``.
    ``block_d=None`` lets each pass take the kernel family's tile, which
    it derives from the fog block's client count and P
    (``kernels.delta_pipeline.delta_pipeline.tile_columns``) and whose
    ragged last block it masks; an int forces the tile.
    """
    from repro.kernels.delta_pipeline.ops import delta_pipeline_partial
    from repro.kernels.delta_pipeline.sharded import combine_epilogue

    c, _ = updates.shape
    if c % fog_nodes:
        raise ValueError(
            f"client count {c} not divisible by fog_nodes {fog_nodes}"
        )
    per_fog = c // fog_nodes
    has_mu = momentum is not None and server_optimizer in (
        "fedavgm", "fedadam"
    )
    m = mask.astype(jnp.float32) * weights.astype(jnp.float32)
    if staleness is not None:
        s = jnp.maximum(jnp.asarray(staleness, jnp.float32), 0.0)
        dm = m * (1.0 + s) ** (-jnp.asarray(staleness_exponent, jnp.float32))
    else:
        dm = m

    partials, sdm, sm = [], [], []
    for f in range(fog_nodes):
        sl = slice(f * per_fog, (f + 1) * per_fog)
        partials.append(
            delta_pipeline_partial(
                updates[sl], dm[sl],
                clip_norm=clip_norm, compression=compression,
                topk_fraction=topk_fraction, seg_sizes=seg_sizes,
                block_d=block_d, interpret=interpret,
            )
        )
        sdm.append(jnp.sum(dm[sl]))
        sm.append(jnp.sum(m[sl]))
    agg_sum = sum(partials[1:], partials[0])
    out, mu2 = combine_epilogue(
        agg_sum, sum(sdm[1:], sdm[0]), sum(sm[1:], sm[0]), base,
        jnp.asarray(lr, jnp.float32),
        has_stale=staleness is not None,
        dp_noise=dp_noise,
        momentum=momentum if has_mu else None,
        server_optimizer=server_optimizer,
        server_momentum=server_momentum,
    )
    if has_mu:
        return out, mu2
    return out


def validate_fog_config(
    fog_nodes: int, num_clients: int, aggregator: str
) -> None:
    """Shared fog-tier config validation for every engine entry point."""
    if fog_nodes < 1:
        raise ValueError(f"fog_nodes must be >= 1, got {fog_nodes}")
    if fog_nodes == 1:
        return
    if num_clients % fog_nodes:
        raise ValueError(
            f"fog_nodes={fog_nodes} must divide the cohort size "
            f"{num_clients}"
        )
    if aggregator != "fedavg":
        raise ValueError(
            f"aggregator={aggregator!r} is an order statistic over the "
            "full client axis; it does not decompose into fog partials "
            "(fog_nodes > 1 requires aggregator='fedavg')"
        )
