"""The FedFog round — the paper's Fig. 1 dataflow as ONE jittable step.

    schedule (Eqs. 1/2/3/7/10, over the N-client registry)
      └─ slot occupancy: top-C eligible clients by utility
    local training (Eq. 5): C slots × E local steps, fresh inner optimizer
      (serverless/stateless semantics), vmap over the slot axis — NO
      cross-client collectives during local steps (the paper's
      communication-reduction payoff)
    deltas: clip (DP sensitivity) → attacks (eval) → compression
    aggregate (Eq. 6): masked weighted reduction over the slot axis — the
      ONE inter-client collective per round (all-reduce over pod×client)
    server update: FedAvg / FedAvgM / FedAdam on the aggregated delta
    bookkeeping: cold starts (Eq. 4), energy (Eq. 10 + §IV.F), drift state

`make_round_fn` returns `round_fn(state, batch) -> (state, metrics)` ready
for jax.jit with the shardings from dist/sharding.py. Shape-static
throughout: masks, not dynamic sets.

The round's stages run under three ``jax.named_scope``s, which tag every
compiled instruction's ``op_name`` metadata and change nothing else:
``fedfog.schedule`` (stage 1), ``fedfog.local_train`` (stage 2) and
``fedfog.server`` (stages 3 to 6). ``dist.hlo_analysis`` maps the compiled
round's instructions to them (``HLOAnalysis.phases``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg_mod
from repro.core import privacy as privacy_mod
from repro.core.scheduler import account_energy, schedule_round
from repro.core.selection import random_selection_mask
from repro.fl import attacks as attacks_mod
from repro.fl import fog as fog_mod
from repro.fl.compression import apply_compression, wire_bytes_per_param
from repro.fl.fuse import (
    fuse_clients,
    fuse_vector,
    fused_gaussian_noise,
    stacked_leaf_sizes,
)
from repro.fl.state import FLConfig, FLState
from repro.kernels.delta_pipeline import (
    delta_pipeline_apply,
    delta_pipeline_apply_sharded,
)
from repro.models.transformer import Runtime
from repro.optim import adamw, apply_updates, clip_by_global_norm, sgdm
from repro.sim.des import RoundCostModel
from repro.sim.faults import config as faults_config
from repro.sim.faults import inject as faults_inject

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class AttackConfig:
    kind: str = "none"  # none|label_flip|noise|dropout|model_replacement
    fraction: float = 0.0  # fraction of malicious slots
    noise_scale: float = 0.5
    replacement_scale: float = 10.0


def _inner_optimizer(fl_cfg: FLConfig):
    if fl_cfg.inner_optimizer == "adamw":
        return adamw(fl_cfg.inner_lr)
    return sgdm(fl_cfg.inner_lr, fl_cfg.inner_momentum)


def _slot_assignment(decision, fl_cfg: FLConfig, rng: Array):
    """Top-C eligible clients by utility -> (slot_client_ids, slot_mask).

    Policies (§IV.B): fedfog = utility-ranked eligible; rcs = uniform random;
    fogfaas/vanilla = fixed round-robin over all clients (no gating).
    """
    n, c = fl_cfg.num_clients, fl_cfg.slots
    sel = decision.selection
    if fl_cfg.policy == "fedfog":
        # Sort by (eligible desc, utility desc): eligible clients first.
        key_val = sel.utility - 1e6 * (~sel.mask)
        order = jnp.argsort(-key_val, stable=True)
        slot_ids = order[:c].astype(jnp.int32)
        slot_mask = sel.mask[slot_ids]
    elif fl_cfg.policy == "rcs":
        rmask = random_selection_mask(rng, n, c)
        order = jnp.argsort(-rmask.astype(jnp.int32), stable=True)
        slot_ids = order[:c].astype(jnp.int32)
        slot_mask = rmask[slot_ids]
    else:  # fogfaas / vanilla: first C clients, no FL-aware gating
        slot_ids = jnp.arange(c, dtype=jnp.int32)
        slot_mask = jnp.ones((c,), bool)
    return slot_ids, slot_mask


def make_round_fn(
    model,
    fl_cfg: FLConfig,
    runtime: Runtime = Runtime(),
    attack: AttackConfig = AttackConfig(),
    *,
    flops_per_client_round: float | None = None,
    rules=None,
):
    """Build the jittable FedFog round.

    batch dict (leading dims slot-major):
      tokens:          (global_batch, S+1) int32  [reshaped to (C, B_c, S+1)]
      patch_embeds / frames: optional modality inputs, (global_batch, ...)
      slot_data_sizes: (C,) f32 — |D_i| of each slot occupant
      telemetry_cpu/mem/batt/energy: (N,) f32
      hist:            (N, hist_bins) f32
    """
    c = fl_cfg.slots
    init_inner, update_inner = _inner_optimizer(fl_cfg)
    flops_round = flops_per_client_round or 0.0
    # §IV.F cost accounting shared with the paper-scale simulator — both
    # engines derive energy/cold-start semantics from the same model.
    cost_model = RoundCostModel.from_scheduler(fl_cfg.scheduler)
    # Pallas-fused delta pipeline: clip → compression emulation →
    # aggregate (Eq. 6 / in-kernel median / trimmed) → DP noise → server
    # momentum → apply, in ONE HBM pass over the fused (C, P) buffer
    # (plus a norm-reduction pass when clipping — kernels/delta_pipeline).
    # Single-host: every aggregator runs in-kernel; delta attacks
    # (noise/dropout/model_replacement) land between clip and compress,
    # so those two stages split out of the kernel (clip+corrupt outside,
    # compression onward fused). Under mesh `rules` the sharded entry
    # (`delta_pipeline_apply_sharded`) runs the same pipeline per client
    # shard with exactly ONE cross-shard psum — the one-all-reduce HLO
    # contract holds on the fast path too. Robust aggregators need the
    # full client axis on-device to sort, so under rules they keep the
    # reference (fused-buffer all-reduce) path. Full matrix:
    # docs/EXPERIMENTS.md "Pipeline-kernel gates".
    use_pallas = fl_cfg.use_pallas_agg and rules is None
    use_pallas_sharded = (
        fl_cfg.use_pallas_agg
        and rules is not None
        and fl_cfg.aggregator == "fedavg"
        and attack.kind == "none"
    )
    # Population mode: the scheduler registry is (M,)-sized; each round
    # gathers a stratified N-client window's rows and scatters them back.
    # Dense mode (population unset or == num_clients) keeps the flat
    # round VERBATIM — bitwise oracle discipline.
    pop_mode = (
        fl_cfg.population is not None
        and fl_cfg.population != fl_cfg.num_clients
    )
    # Fault layer (repro.sim.faults): Python-level gate — with the plan
    # off, every line below is the verbatim pre-fault round (bitwise
    # contract, same as the paper-scale simulator's gate).
    faults_on = faults_config.active(fl_cfg.faults)

    # Pod-scale sharding constraints: pin the slot-stacked replicas to the
    # client axis (and moments to the ZeRO axis) instead of trusting GSPMD
    # propagation through the broadcast.
    if rules is not None:
        shapes, laxes = model.param_shapes(), model.param_axes()
        _stacked = rules.shardings(
            rules.param_specs(shapes, laxes, stacked=True)
        )
        _stacked_opt = rules.shardings(
            rules.opt_spec_tree(shapes, laxes, stacked=True)
        )

        def constrain_stacked(t):
            return jax.lax.with_sharding_constraint(t, _stacked)

        def constrain_opt_tree(t):
            return jax.lax.with_sharding_constraint(t, _stacked_opt)

        from jax.sharding import NamedSharding, PartitionSpec as P

        _client_ent = rules._as_spec_entry(rules.plan.client_axes)
        _zero_ent = "zero" if "zero" in rules.mesh.shape else None

        def fuse_deltas(tree, shard_p=True):
            """Concat every delta leaf into ONE (C, P) f32 buffer so the
            cross-client aggregation lowers to a single all-reduce — the
            paper's one-collective-per-round contract, asserted by
            dist.hlo_analysis on the compiled round. Returns the buffer
            and the inverse (split + reshape + cast back).
            ``shard_p=False`` gives the sharded-kernel layout (client
            axis split, full P rows per shard)."""
            cat, unfuse = fuse_clients(tree)
            cat = jax.lax.with_sharding_constraint(
                cat,
                rules.fused_delta_sharding(cat.shape[1], shard_p=shard_p),
            )
            return cat, unfuse

        def constrain_batch(tree):
            """Pin slot-major batches to (client, zero, ...) so activations
            keep the intra-slot data sharding through the reshape."""
            def one(x):
                spec = P(_client_ent, _zero_ent, *([None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(rules.mesh, spec)
                )

            return jax.tree.map(one, tree)
    else:
        constrain_stacked = constrain_opt_tree = lambda t: t
        constrain_batch = lambda t: t
        fuse_deltas = None

    def per_slot_loss(params_c, batch_c):
        return model.loss(params_c, batch_c, runtime)

    def round_fn(state: FLState, batch) -> tuple[FLState, dict]:
        from repro.core.types import ClientTelemetry

        with jax.named_scope("fedfog.schedule"):
            rng, k_sched, k_attack, k_dp, k_mal = jax.random.split(state.rng, 5)

            # ---- 1. schedule over the N-client registry (Eqs. 1/2/3/7) ---- #
            telemetry = ClientTelemetry(
                cpu=batch["telemetry_cpu"],
                mem=batch["telemetry_mem"],
                batt=batch["telemetry_batt"],
                energy=batch["telemetry_energy"],
            )
            if pop_mode:
                # Sample the round's scheduling window from the (M,) registry
                # (fold_in key 7 — disjoint from the 5-way round split) and
                # gather its scheduler rows; the batch's telemetry/hist rows
                # are window-positional (the caller feeds N rows for the
                # window, not the whole population).
                window_ids = fog_mod.stratified_cohort(
                    jax.random.fold_in(state.rng, 7),
                    fl_cfg.population, fl_cfg.num_clients,
                )
                sched_view = fog_mod.gather_sched_rows(state.sched, window_ids)
            else:
                window_ids = None
                sched_view = state.sched
            decision = schedule_round(
                sched_view, telemetry, batch["hist"], fl_cfg.scheduler
            )
            slot_ids, slot_mask = _slot_assignment(decision, fl_cfg, k_sched)
            slot_sizes = batch["slot_data_sizes"]

        with jax.named_scope("fedfog.local_train"):
            # ---- 2. local training: C slots × E local steps --------------- #
            def to_slots(x):
                return x.reshape((c, x.shape[0] // c) + x.shape[1:])

            model_batch = constrain_batch(
                {
                    k: to_slots(v)
                    for k, v in batch.items()
                    if k in ("tokens", "patch_embeds", "frames")
                }
            )
            if attack.kind == "label_flip":
                n_mal = int(round(attack.fraction * c))
                malicious = jnp.arange(c) < n_mal
                malicious = jax.random.permutation(k_mal, malicious)
                model_batch["tokens"] = attacks_mod.flip_labels(
                    model_batch["tokens"], malicious, model.cfg.vocab_size
                )
            elif attack.kind != "none":
                n_mal = int(round(attack.fraction * c))
                malicious = jax.random.permutation(
                    k_mal, jnp.arange(c) < n_mal
                )
            else:
                malicious = jnp.zeros((c,), bool)

            params0 = state.params
            params_stacked = constrain_stacked(
                jax.tree.map(
                    lambda p: jnp.broadcast_to(p[None], (c,) + p.shape), params0
                )
            )
            inner_state = init_inner(params_stacked)
            inner_state = inner_state._replace(
                mu=constrain_opt_tree(inner_state.mu),
                nu=None if inner_state.nu is None
                else constrain_opt_tree(inner_state.nu),
            )

            grad_fn = jax.vmap(jax.value_and_grad(per_slot_loss))

            if fl_cfg.microbatch > 1:
                # Gradient accumulation: scan over micro-splits of each slot's
                # batch, accumulating fp32 grads. Bounds live activations to one
                # microbatch's worth — the decisive train-memory knob at 14B+.
                mb = fl_cfg.microbatch

                def grad_fn(params_s, batch_s):  # noqa: F811
                    micro = {
                        k: jnp.moveaxis(
                            v.reshape(
                                (v.shape[0], mb, v.shape[1] // mb)
                                + v.shape[2:]
                            ),
                            1, 0,
                        )
                        for k, v in batch_s.items()
                    }

                    def acc_step(carry, mbatch):
                        g_acc, l_acc = carry
                        loss, g = jax.vmap(jax.value_and_grad(per_slot_loss))(
                            params_s, mbatch
                        )
                        g_acc = jax.tree.map(
                            lambda a, b: a + b.astype(jnp.float32), g_acc, g
                        )
                        return (g_acc, l_acc + jnp.mean(loss)), None

                    g0 = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params_s
                    )
                    (g, l), _ = jax.lax.scan(acc_step, (g0, jnp.zeros(())), micro)
                    g = jax.tree.map(lambda a: (a / mb), g)
                    return l / mb, g

            if fl_cfg.local_steps == 1:
                loss, grads = grad_fn(params_stacked, model_batch)
                updates, inner_state2 = update_inner(
                    grads, inner_state, params_stacked
                )
                params_stacked = apply_updates(params_stacked, updates)
                mean_loss = jnp.mean(loss)
            else:
                # Split each slot's batch into E microbatches along the batch dim.
                e = fl_cfg.local_steps

                def split_steps(x):  # (C, B_c, ...) -> (E, C, B_c/E, ...)
                    b_c = x.shape[1]
                    return jnp.moveaxis(
                        x.reshape((c, e, b_c // e) + x.shape[2:]), 1, 0
                    )

                micro = {k: split_steps(v) for k, v in model_batch.items()}

                def one_step(carry, mb):
                    params_s, inner, _ = carry
                    loss, grads = grad_fn(params_s, mb)
                    updates, inner = update_inner(grads, inner, params_s)
                    params_s = apply_updates(params_s, updates)
                    return (params_s, inner, jnp.mean(loss)), None

                (params_stacked, inner_state2, mean_loss), _ = jax.lax.scan(
                    one_step, (params_stacked, inner_state, jnp.zeros(())), micro
                )
            del inner_state2

        with jax.named_scope("fedfog.server"):
            # ---- 3. deltas: clip → attack → compress ---------------------- #
            deltas = jax.tree.map(
                lambda p, p0: (
                    p.astype(jnp.float32) - p0.astype(jnp.float32)[None]
                ).astype(p.dtype),
                params_stacked,
                params0,
            )
            use_kernel = use_pallas or use_pallas_sharded
            # Delta attacks land BETWEEN clip and compress, so when the
            # kernel path is on those two stages split: reference clip +
            # corrupt here, compression onward stays fused (the kernel then
            # runs with clip_norm=0).
            split_clip = use_kernel and attack.kind not in ("none", "label_flip")
            if not use_kernel:
                # Reference pipeline: one XLA pass per stage per leaf. On
                # the fused path these stages all fold into the kernel call
                # below.
                if fl_cfg.clip_norm > 0:
                    deltas = jax.vmap(
                        lambda d: clip_by_global_norm(d, fl_cfg.clip_norm)[0]
                    )(deltas)
                if attack.kind not in ("none", "label_flip"):
                    deltas = attacks_mod.corrupt_deltas(
                        deltas, malicious, attack.kind, k_attack,
                        noise_scale=attack.noise_scale,
                        replacement_scale=attack.replacement_scale,
                    )
                    slot_mask = attacks_mod.dropout_mask(
                        slot_mask, malicious, attack.kind
                    )
                deltas = apply_compression(
                    deltas, fl_cfg.compression, fl_cfg.topk_fraction
                )
            elif split_clip:
                if fl_cfg.clip_norm > 0:
                    deltas = jax.vmap(
                        lambda d: clip_by_global_norm(d, fl_cfg.clip_norm)[0]
                    )(deltas)
                deltas = attacks_mod.corrupt_deltas(
                    deltas, malicious, attack.kind, k_attack,
                    noise_scale=attack.noise_scale,
                    replacement_scale=attack.replacement_scale,
                )
                slot_mask = attacks_mod.dropout_mask(
                    slot_mask, malicious, attack.kind
                )

            # ---- 3b. fault plan: who actually arrives (repro.sim.faults) -- #
            # Slot-level serverless failure plan: retries with backoff, fog
            # outages, deadline losses and the quorum decision, drawn from a
            # key chain disjoint from the round's 5-way split (fold_in 11) so
            # faulted runs replay deterministically per seed. The arrival
            # mask replaces ``slot_mask`` BEFORE aggregation, so Eq. 6
            # reweights over the arrivals only, on every aggregation path
            # (reference, fog tier, fused kernel, sharded kernel).
            fault_counters = faults_inject.zero_counters()
            fault_skip = None
            fault_round_ms = None
            if faults_on:
                fc = fl_cfg.faults
                k_fplan, k_fnoise = jax.random.split(
                    jax.random.fold_in(state.rng, 11)
                )
                # Under mesh rules the plan must run as a replicated island:
                # its (slots,) pred chains mix gathers from client-sharded
                # arrays, and letting the SPMD partitioner reshard those mid-
                # chain has been observed to MISCOMPILE (spmd_partitioner
                # "involuntary full rematerialization" + wrong fail masks),
                # breaking sharded-vs-plain fault replay. The arrays are
                # tiny, so replication is free.
                _rep = (
                    (lambda t: jax.tree.map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, NamedSharding(rules.mesh, P())
                        ), t))
                    if rules is not None else (lambda t: t)
                )
                plan = faults_inject.plan_round(
                    fc, k_fplan, _rep(slot_mask),
                    _rep(~sched_view.warm[slot_ids]),
                    _rep(decision.delays_ms[slot_ids]),
                    fog_nodes=fl_cfg.fog_nodes,
                )
                plan = _rep(plan)
                # Partitionable threefry for the payload noise: legacy
                # (non-partitionable) threefry draws DIFFERENT bits under a
                # multi-device lowering depending on the leaf's sharding
                # spec, which would make a faulted sharded round diverge
                # from its single-host replay by O(corrupt_scale). The
                # context only rebinds the bit generator for these draws.
                with jax.threefry_partitionable(True):
                    deltas = attacks_mod.corrupt_deltas(
                        deltas, plan.corrupt, "noise", k_fnoise,
                        noise_scale=fc.corrupt_scale,
                    )
                slot_mask = plan.arrived
                fault_counters = plan.counters
                fault_skip = plan.skip
                fault_round_ms = plan.round_ms

            # ---- 4+5. aggregate (Eq. 6) + server update ------------------- #
            if use_kernel:
                # Fused delta-pipeline kernel: clip, compression emulation,
                # aggregation, DP noise, server momentum and the apply all
                # happen in one pass over the fused (C, P) buffer — the
                # memory-bound pipeline never re-reads the delta stack from
                # HBM (clipping adds one norm-reduction pass). Under mesh
                # rules the buffer is client-sharded and the sharded entry
                # combines per-shard partial sums with ONE psum.
                if use_pallas_sharded:
                    cat_d, _ = fuse_deltas(deltas, shard_p=False)
                else:
                    cat_d, _ = fuse_clients(deltas)
                base_flat, unfuse_vec = fuse_vector(params0)
                seg = stacked_leaf_sizes(deltas)
                noise = None
                if fl_cfg.dp_sigma > 0:
                    noise = fused_gaussian_noise(
                        k_dp,
                        fl_cfg.dp_sigma * (fl_cfg.clip_norm or 1.0),
                        seg,
                        [x.shape for x in jax.tree.leaves(params0)],
                    )
                mu_flat = unfuse_mu = None
                if (
                    fl_cfg.server_optimizer in ("fedavgm", "fedadam")
                    and state.server_mu is not None
                ):
                    mu_flat, unfuse_mu = fuse_vector(state.server_mu)
                kernel_clip = 0.0 if split_clip else fl_cfg.clip_norm
                kw = dict(
                    lr=fl_cfg.server_lr, dp_noise=noise, momentum=mu_flat,
                    clip_norm=kernel_clip,
                    compression=fl_cfg.compression,
                    topk_fraction=fl_cfg.topk_fraction,
                    seg_sizes=seg,
                    server_optimizer=fl_cfg.server_optimizer,
                    server_momentum=fl_cfg.server_momentum,
                )
                if use_pallas_sharded:
                    outs = delta_pipeline_apply_sharded(
                        cat_d, base_flat, slot_mask, slot_sizes,
                        mesh=rules.mesh, client_axes=rules.plan.client_axes,
                        fog_nodes=fl_cfg.fog_nodes,
                        **kw,
                    )
                elif fl_cfg.fog_nodes > 1:
                    # Single-host fog tier: one delta_pipeline_partial pass
                    # per fog's contiguous slot block + the shared cloud
                    # epilogue (fl/fog.py; fedavg-only, enforced by config).
                    outs = fog_mod.fog_pipeline_apply(
                        cat_d, base_flat, slot_mask, slot_sizes,
                        fog_nodes=fl_cfg.fog_nodes,
                        **kw,
                    )
                else:
                    outs = delta_pipeline_apply(
                        cat_d, base_flat, slot_mask, slot_sizes,
                        trim_fraction=fl_cfg.trim_fraction,
                        aggregator=fl_cfg.aggregator,
                        **kw,
                    )
                if mu_flat is not None:
                    new_flat, new_mu_flat = outs
                    new_mu = unfuse_mu(new_mu_flat)
                else:
                    new_flat, new_mu = outs, state.server_mu
                new_params = unfuse_vec(new_flat)
                new_count = state.server_count + 1
            else:
                # On the pod-scale path the leaves are fused into one (C, P)
                # buffer first, so ALL the cross-client traffic of the round
                # is a single all-reduce instead of one per parameter tensor.
                agg_in, unfuse = (
                    fuse_deltas(deltas) if fuse_deltas is not None
                    else (deltas, None)
                )
                if fl_cfg.aggregator == "median":
                    agg = agg_mod.median_aggregate(agg_in, slot_mask)
                elif fl_cfg.aggregator == "trimmed":
                    agg = agg_mod.trimmed_mean_aggregate(
                        agg_in, slot_mask, fl_cfg.trim_fraction
                    )
                elif fl_cfg.fog_nodes > 1:
                    # Hierarchical Eq. 6 on the reference path: fog partials
                    # → cloud combine (float-reassociated flat aggregate).
                    if unfuse is not None:
                        agg = fog_mod.fog_aggregate(
                            agg_in, slot_mask, slot_sizes, fl_cfg.fog_nodes
                        )
                    else:
                        agg = fog_mod.fog_aggregate_tree(
                            agg_in, slot_mask, slot_sizes, fl_cfg.fog_nodes
                        )
                else:
                    agg = agg_mod.fedavg_stacked(agg_in, slot_mask, slot_sizes)
                if unfuse is not None:
                    agg = unfuse(agg)
                if fl_cfg.dp_sigma > 0:
                    dp = privacy_mod.DPConfig(
                        sigma=fl_cfg.dp_sigma,
                        sensitivity=fl_cfg.clip_norm or 1.0,
                    )
                    agg = privacy_mod.gaussian_mechanism(agg, k_dp, dp)
                new_params, new_mu, new_count = _server_update(
                    fl_cfg, params0, agg, state.server_mu, state.server_count
                )

            if fault_skip is not None:
                # Below-quorum round: the model (and server optimizer state)
                # carries over bitwise — the attempted aggregate is discarded.
                new_params = jax.tree.map(
                    lambda p, q: jnp.where(fault_skip, p, q), params0, new_params
                )
                if state.server_mu is not None:
                    new_mu = jax.tree.map(
                        lambda p, q: jnp.where(fault_skip, p, q),
                        state.server_mu, new_mu,
                    )
                new_count = jnp.where(fault_skip, state.server_count, new_count)

            # ---- 6. energy / cold-start / drift bookkeeping --------------- #
            # Per-LOGICAL-client energy: compute ∝ FLOPs for selected clients,
            # uplink ∝ compressed delta bytes (§IV.F) — via the shared DES
            # cost model (repro.sim.des).
            tx_bytes = wire_bytes_per_param(
                fl_cfg.compression, fl_cfg.topk_fraction
            ) * float(model.param_count())
            round_energy_j = cost_model.energy_j(
                decision.selection.mask, sched_view.warm, flops_round, tx_bytes
            )
            if faults_on:
                # Every launched attempt repays the slot's full per-round
                # energy (a crashed function restarts from the global model);
                # non-slot selected clients keep the 1× baseline.
                round_energy_j = round_energy_j * (
                    jnp.ones_like(round_energy_j)
                    .at[slot_ids]
                    .set(jnp.maximum(plan.attempts, 1.0))
                )
            advanced = account_energy(
                decision.new_state, round_energy_j, fl_cfg.scheduler
            )
            if pop_mode:
                # Scatter the window's advanced rows back into the (M,)
                # registry; unsampled clients stay frozen until next sampled.
                new_sched = fog_mod.scatter_sched_rows(
                    state.sched, window_ids, advanced
                )
            else:
                new_sched = advanced

            new_state = FLState(
                params=new_params,
                server_mu=new_mu,
                server_count=new_count,
                sched=new_sched,
                rng=rng,
                step=state.step + 1,
            )
            metrics = {
                "loss": mean_loss,
                "num_selected": decision.selection.num_selected,
                "slot_participation": jnp.sum(slot_mask.astype(jnp.int32)),
                "cold_starts": decision.cold_starts,
                # Synchronous round latency = slowest selected client (§III.H);
                # under faults the retry/backoff chain (deadline-capped).
                "round_latency_ms": (
                    fault_round_ms
                    if fault_round_ms is not None
                    else jnp.max(
                        jnp.where(slot_mask, decision.delays_ms[slot_ids], 0.0)
                    )
                ),
                "energy_j": jnp.sum(round_energy_j),
                "mean_utility": jnp.mean(decision.selection.utility),
                "mean_drift": jnp.mean(decision.selection.drift),
                # Fault/recovery counters — structurally always present
                # (zeros when the plan is off) so history schemas are stable
                # across faulted and clean runs.
                **fault_counters,
            }
            return new_state, metrics

    return round_fn


def _server_update(fl_cfg: FLConfig, params0, agg, mu, count):
    lr = fl_cfg.server_lr
    count = count + 1
    if fl_cfg.server_optimizer == "fedavg" or mu is None:
        new_params = jax.tree.map(
            lambda p, a: (p.astype(jnp.float32) + lr * a.astype(jnp.float32)).astype(
                p.dtype
            ),
            params0,
            agg,
        )
        return new_params, mu, count
    m = fl_cfg.server_momentum
    new_mu = jax.tree.map(
        lambda mu_l, a: m * mu_l + a.astype(jnp.float32), mu, agg
    )
    if fl_cfg.server_optimizer == "fedadam":
        # Adam-style with a fixed epsilon on the aggregated delta magnitude.
        new_params = jax.tree.map(
            lambda p, mu_l, a: (
                p.astype(jnp.float32)
                + lr * mu_l / (jnp.sqrt(jnp.square(a.astype(jnp.float32))) + 1e-3)
            ).astype(p.dtype),
            params0,
            new_mu,
            agg,
        )
    else:  # fedavgm
        new_params = jax.tree.map(
            lambda p, mu_l: (p.astype(jnp.float32) + lr * mu_l).astype(p.dtype),
            params0,
            new_mu,
        )
    return new_params, new_mu, count
