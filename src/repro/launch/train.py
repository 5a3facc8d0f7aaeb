"""Federated training driver (pod-scale path on real hardware; CPU-scaled
here). Wires: configs → model → mesh plan + sharding rules → FedFog round
→ data pipeline → checkpointing, with auto-resume.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --rounds 100 --scale tiny --ckpt-dir /tmp/fedfog_ckpt

``--scale tiny`` substitutes the reduced config + a 1-device plan so the
full training loop (including checkpoint/restart) runs on a CPU host.
``--scale full`` is the distribution-aware path: it builds the mesh plan
from ``repro.dist`` for the device pool it runs on, jits the round with
in/out shardings from ``ShardingRules`` and verifies via ``analyze_hlo``
that the compiled round contains exactly the paper's ONE inter-client
all-reduce. A 256-chip pod gets the production mesh; any other pool a
client × zero host plan (one chip: 1 × 1, four chips: 2 × 2). ``--layers``
cuts the depth, in whole periods of the layer pattern, for a pool that
cannot hold every layer; widths are never cut. On CPU, back the plan with
fake devices:

    python -m repro.launch.train --scale full --devices 256 --compile-only
    python -m repro.launch.train --scale full --devices 8 \
        --reduced --rounds 2          # actually executes sharded rounds
    python -m repro.launch.train --scale full --arch rwkv6-1.6b \
        --layers 5 --slots 2 --pallas-agg --rounds 3   # one TPU chip
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any, NamedTuple

from repro.obs import spans


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch-per-slot", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--inner-lr", type=float, default=0.05)
    ap.add_argument("--track", default="",
                    help="stream per-round metrics to a tracker spec: "
                         "'jsonl:PATH', 'csv:PATH', comma-separated for "
                         "multiple sinks, '' disables (see repro.obs)")
    ap.add_argument("--track-every", type=int, default=1,
                    help="decimation for --track: log every k-th round")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # --scale full knobs
    ap.add_argument("--devices", type=int, default=0,
                    help="back the full-scale mesh with N fake CPU devices "
                         "(XLA_FLAGS; must be set before jax initializes). "
                         "0 = use the real platform's device pool")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fog-nodes", type=int, default=1,
                    help="fog-tier width of the edge->fog->cloud "
                         "reduction; under a multi-pod mesh this must "
                         "equal the pod count (fog <-> pod axis), and "
                         "the HLO contract is asserted per tier")
    ap.add_argument("--population", type=int, default=None,
                    help="virtual client registry size (>= --clients); "
                         "rounds gather a stratified --clients window")
    ap.add_argument("--pallas-agg", action="store_true",
                    help="fuse the server delta pipeline into the Pallas "
                         "kernel (sharded shard_map entry under --scale "
                         "full; single-HBM-pass kernel on one host)")
    ap.add_argument("--fault-timeout-rate", type=float, default=0.0,
                    help="cold-start timeout probability (attempt 0)")
    ap.add_argument("--fault-crash-rate", type=float, default=0.0,
                    help="per-attempt function-crash probability")
    ap.add_argument("--fault-drop-rate", type=float, default=0.0,
                    help="per-attempt payload-drop probability")
    ap.add_argument("--fault-corrupt-rate", type=float, default=0.0,
                    help="arrived-payload corruption probability")
    ap.add_argument("--fault-partition-rate", type=float, default=0.0,
                    help="per-round transient network-partition probability")
    ap.add_argument("--fault-fog-outage-rate", type=float, default=0.0,
                    help="per-round per-fog-node outage probability")
    ap.add_argument("--fault-failover", action="store_true",
                    help="reassign a dead fog's clients to survivors")
    ap.add_argument("--fault-retries", type=int, default=0,
                    help="per-client retry cap (exponential backoff)")
    ap.add_argument("--fault-deadline-ms", type=float, default=None,
                    help="server round deadline (None = barrier)")
    ap.add_argument("--fault-quorum", type=float, default=0.0,
                    help="min arrived/admitted fraction to aggregate; "
                         "below quorum the round is skipped")
    ap.add_argument("--reduced", action="store_true",
                    help="with --scale full: reduced config on the real "
                         "mesh plan (CPU-executable sharded rounds)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --scale full: cut the published depth to N "
                         "layers (whole periods of the layer pattern; "
                         "widths unchanged). 0 = published depth")
    ap.add_argument("--compile-only", action="store_true",
                    help="with --scale full: lower+compile the sharded "
                         "round, report collectives, skip execution")
    return ap.parse_args(argv)


def fault_config_from_args(args):
    """Build the round's ``FaultConfig`` from ``--fault-*`` flags; None
    when every knob is at its faults-off default (the round then takes
    its verbatim pre-fault path)."""
    rates = dict(
        timeout_rate=args.fault_timeout_rate,
        crash_rate=args.fault_crash_rate,
        drop_rate=args.fault_drop_rate,
        corrupt_rate=args.fault_corrupt_rate,
        partition_rate=args.fault_partition_rate,
        fog_outage_rate=args.fault_fog_outage_rate,
    )
    if not any(rates.values()) and args.fault_deadline_ms is None:
        return None
    from repro.sim.faults import FaultConfig

    return FaultConfig(
        **rates,
        fog_failover=args.fault_failover,
        max_retries=args.fault_retries,
        deadline_ms=args.fault_deadline_ms,
        quorum_frac=args.fault_quorum,
    )


@dataclasses.dataclass(frozen=True)
class RoundProgram:
    """The compiled round and what readers need of it: ``phases`` (HLO
    instruction -> ``fedfog.*`` scope) and the HLO ``module`` name.
    ``input_shardings`` is the executable's. A call dispatches one round
    inside the ``fedfog.round`` span."""

    compiled: Any
    phases: dict = dataclasses.field(default_factory=dict, repr=False)
    module: str = ""

    @property
    def input_shardings(self):
        return self.compiled.input_shardings

    def __call__(self, state, batch):
        with spans.span("fedfog.round"):
            return self.compiled(state, batch)


class TrainRun(NamedTuple):
    """What :func:`main` ran: the final round state, one dict of host
    metrics per round, and the model and FL config it built."""

    state: Any
    history: list
    model: Any
    fl_cfg: Any


def init_state(model, fl_cfg, seed: int, sharding=None):
    """The round's starting state for ``--seed``, made by one jitted
    program, straight into ``sharding`` when one is given."""
    import jax

    from repro.fl import init_fl_state

    with spans.span("fedfog.setup.init_state"):
        return jax.block_until_ready(jax.jit(
            lambda k: init_fl_state(model, fl_cfg, k), out_shardings=sharding
        )(jax.random.PRNGKey(seed)))


def main(argv=None, devices=None):
    """Run the training loop; returns a :class:`TrainRun` (None with
    ``--compile-only``). ``devices`` restricts the ``--scale full`` plan
    to that device list (default: every local device)."""
    args = parse_args(argv)
    if args.scale == "full" and args.devices:
        # Must precede the first jax backend init in this process.
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )
    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()

    from repro import checkpoint as ckpt
    from repro.data.synthetic import (
        FedDataConfig,
        all_client_histograms,
        client_data_sizes,
        round_batch,
    )
    from repro.data.telemetry import (
        TelemetryConfig,
        init_telemetry,
        make_profiles,
        step_telemetry,
    )
    from repro.fl import FLConfig, init_fl_state, make_round_fn
    from repro.launch import config_from_args
    from repro.models import Runtime, build_model
    from repro.obs import tracker_from_spec

    full = args.scale == "full"
    cfg = config_from_args(args)
    model = build_model(cfg)

    rules = None
    if full:
        from repro.dist import make_rules
        from repro.launch import mesh as mesh_mod

        pods = 2 if args.multi_pod else 1
        pool = args.devices or len(devices or jax.devices())
        if pool == mesh_mod.CHIPS_PER_POD * pods and devices is None:
            pm = mesh_mod.make_production_mesh(multi_pod=args.multi_pod)
            rules = make_rules(pm, cfg, multi_pod=args.multi_pod)
        else:
            # Host plan (client × zero only) on the pool's devices.
            rules = make_rules(
                None, cfg, multi_pod=args.multi_pod, device_count=pool,
                devices=devices,
            )
        # Slots fill the client ways: a multiple of them, each client
        # shard vmapping its share of the slots.
        ways = rules.client_ways
        args.slots = ways * -(-args.slots // ways)
        args.clients = max(args.clients, 2 * args.slots)
        print(f"[train] mesh plan: {dict(rules.mesh.shape)} "
              f"slots={args.slots} layers={cfg.num_layers} "
              f"params={model.param_count():,}")

    fl_cfg = FLConfig(
        num_clients=args.clients,
        slots=args.slots,
        local_steps=args.local_steps,
        inner_lr=args.inner_lr,
        use_pallas_agg=args.pallas_agg,
        fog_nodes=args.fog_nodes,
        population=args.population,
        faults=fault_config_from_args(args),
    )
    data_cfg = FedDataConfig(
        vocab_size=cfg.vocab_size, drift_period=10, seed=args.seed
    )
    tel_cfg = TelemetryConfig(num_clients=args.clients, seed=args.seed)
    profiles = make_profiles(tel_cfg)
    telemetry = init_telemetry(tel_cfg)
    sizes = client_data_sizes(data_cfg, args.clients)

    tokens_per_client = args.batch_per_slot * args.seq_len * args.local_steps
    flops_round = model.flops_per_token() * tokens_per_client

    if rules is not None:
        # Compile against abstract inputs FIRST: --compile-only never
        # allocates full-size parameters on the host.
        round_fn = _sharded_round_fn(args, cfg, model, fl_cfg, rules,
                                     flops_round)
        if args.compile_only:
            return None
    else:
        round_fn = RoundProgram(jax.jit(
            make_round_fn(
                model,
                fl_cfg,
                Runtime(moe_impl="dropless" if cfg.num_experts else "reference"),
                flops_per_client_round=flops_round,
            ),
            donate_argnums=(0,),
        ))

    if rules is not None:
        # Straight into the round's layout: a state made on the first
        # device would stay there beside its sharded copy through round 0
        # and take the memory the round needs.
        state = init_state(model, fl_cfg, args.seed,
                           round_fn.input_shardings[0][0])
    else:
        state = init_fl_state(model, fl_cfg, jax.random.PRNGKey(args.seed))
    start_round = 0
    checkpointer = None
    if args.ckpt_dir:
        checkpointer = ckpt.AsyncCheckpointer(args.ckpt_dir)
        if args.resume:
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                state = ckpt.restore(args.ckpt_dir, latest, state)
                start_round = latest
                print(f"[train] resumed from round {latest}")

    data_key = jax.random.PRNGKey(args.seed + 1)
    tracker = tracker_from_spec(args.track)
    with tracker:
        state, history = _train_loop(
            args, fl_cfg, data_cfg, tel_cfg, round_fn, state, telemetry,
            profiles, sizes, start_round, checkpointer, tracker,
        )
    return TrainRun(state, history, model, fl_cfg)


def _train_loop(args, fl_cfg, data_cfg, tel_cfg, round_fn, state, telemetry,
                profiles, sizes, start_round, checkpointer, tracker):
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import all_client_histograms, round_batch
    from repro.data.telemetry import step_telemetry

    data_key = jax.random.PRNGKey(args.seed + 1)
    history = []
    for r in range(start_round, args.rounds):
        with spans.span("fedfog.round.inputs") as inputs:
            data_key, kb = jax.random.split(data_key)
            r_idx = jnp.asarray(r, jnp.int32)
            # Occupants for this round: previous utility order isn't known
            # host-side before the jit call, so the pipeline streams data
            # for the scheduler's PREDICTED top slots (previous-round
            # order); the round function re-ranks internally. Here:
            # round-robin cohort.
            slot_ids = (
                (jnp.arange(fl_cfg.slots) + r * fl_cfg.slots) % args.clients
            )
            tokens = round_batch(
                data_cfg, slot_ids, r_idx, kb,
                args.batch_per_slot * args.local_steps, args.seq_len,
            )
            batch = {
                "tokens": tokens,
                "slot_data_sizes": sizes[slot_ids],
                "telemetry_cpu": telemetry.cpu,
                "telemetry_mem": telemetry.mem,
                "telemetry_batt": telemetry.batt,
                "telemetry_energy": telemetry.energy,
                "hist": all_client_histograms(
                    data_cfg, args.clients, r_idx, fl_cfg.hist_bins
                ),
            }
        state, metrics = round_fn(state, batch)
        # One device-to-host read of every metric, shared by the history,
        # the tracker and the print.
        with spans.span("fedfog.round.read") as read:
            metrics = jax.device_get(metrics)
        round_wall_s = (inputs.seconds + spans.stats()["fedfog.round"].last_s
                        + read.seconds)
        history.append({k: float(v) for k, v in metrics.items()})
        if r % max(args.track_every, 1) == 0:
            tracker.log(
                {"event": "round", "arch": args.arch, "scale": args.scale,
                 **metrics, "round_wall_s": round_wall_s},
                step=r,
            )
        with spans.span("fedfog.round.telemetry") as tel:
            data_key, kt = jax.random.split(data_key)
            telemetry = step_telemetry(
                tel_cfg,
                telemetry,
                jnp.zeros((args.clients,), bool)
                .at[slot_ids]
                .set(True),
                jnp.zeros((args.clients,)),
                profiles,
                kt,
            )
        print(
            f"[round {r:4d}] loss={float(metrics['loss']):.4f} "
            f"selected={int(metrics['num_selected'])} "
            f"cold={int(metrics['cold_starts'])} "
            f"latency={float(metrics['round_latency_ms']):.0f}ms "
            f"energy={float(metrics['energy_j']):.1f}J "
            + (
                f"retries={int(metrics['fault_retries'])} "
                f"lost={int(metrics['fault_lost'])} "
                f"skipped={int(metrics['round_skipped'])} "
                if fl_cfg.faults is not None
                else ""
            )
            + f"({round_wall_s + tel.seconds:.2f}s)",
            flush=True,
        )
        if checkpointer and (r + 1) % args.ckpt_every == 0:
            with spans.span("fedfog.checkpoint"):
                checkpointer.save(r + 1, state)
    if checkpointer:
        with spans.span("fedfog.checkpoint"):
            checkpointer.wait()
    tracker.log_summary(
        {"arch": args.arch, "scale": args.scale,
         "rounds": args.rounds - start_round,
         "final_loss": history[-1]["loss"] if history else 0.0}
    )
    return state, history


def _sharded_round_fn(args, cfg, model, fl_cfg, rules, flops_round):
    """AOT-compile the round with shardings from the rules against
    abstract inputs (so --compile-only never allocates parameters and
    round 0 doesn't re-trace), and enforce the paper's communication
    contract: exactly ONE inter-client all-reduce (the Eq. 6 delta
    aggregation) in the compiled round body. Returns a
    :class:`RoundProgram`, whose phase map is also registered with
    ``repro.obs.spans``. The compile keys its cache entry on the program's
    metadata, so that the map survives a warm cache."""
    import jax
    import jax.numpy as jnp

    from repro.dist import analyze_hlo
    from repro.dist.hlo_analysis import assert_inter_client_contract
    from repro.fl import abstract_fl_state, make_round_fn
    from repro.launch.compile_cache import metadata_in_key
    from repro.models import Runtime

    mesh_shape = rules.mesh.shape
    runtime = Runtime(
        mesh=rules.mesh,
        batch_axes=rules.batch_axes,
        expert_axis="expert" if cfg.num_experts else None,
        tp_axis="tp" if mesh_shape.get("tp", 1) > 1 else None,
        moe_impl="gshard" if cfg.num_experts else "dropless",
        moe_group_axes=tuple(a for a in ("zero",) if mesh_shape.get(a, 1) > 1),
    )
    round_fn = make_round_fn(
        model, fl_cfg, runtime,
        flops_per_client_round=flops_round, rules=rules,
    )

    state_abs = abstract_fl_state(model, fl_cfg)
    state_sh = rules.shardings(rules.fl_state_specs(model, state_abs))

    gb = fl_cfg.slots * args.batch_per_slot * args.local_steps
    n = fl_cfg.num_clients
    f32 = jnp.float32
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((gb, args.seq_len + 1), jnp.int32),
        "slot_data_sizes": jax.ShapeDtypeStruct((fl_cfg.slots,), f32),
        "telemetry_cpu": jax.ShapeDtypeStruct((n,), f32),
        "telemetry_mem": jax.ShapeDtypeStruct((n,), f32),
        "telemetry_batt": jax.ShapeDtypeStruct((n,), f32),
        "telemetry_energy": jax.ShapeDtypeStruct((n,), f32),
        "hist": jax.ShapeDtypeStruct((n, fl_cfg.hist_bins), f32),
    }
    batch_sh = rules.fl_batch_shardings(batch_abs)

    # out_shardings pins the advanced state to the SAME layout as the
    # input: the compiled object's strict call-time sharding check must
    # accept round r's output as round r+1's input. Without this the
    # sharded kernel path hands params back replicated (the shard_map
    # epilogue's layout) and round 1 rejects them.
    jitted = jax.jit(
        round_fn, in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None), donate_argnums=(0,),
    )
    with spans.span("fedfog.setup.lower") as lower:
        lowered = jitted.lower(state_abs, batch_abs)
    with spans.span("fedfog.setup.compile") as compile_, metadata_in_key():
        compiled = lowered.compile()
    print(f"[train] sharded round compiled in "
          f"{lower.seconds + compile_.seconds:.1f}s")
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"[train] device memory per chip: "
              f"args={mem.argument_size_in_bytes / 1e9:.2f} GB "
              f"temp={mem.temp_size_in_bytes / 1e9:.2f} GB "
              f"out={mem.output_size_in_bytes / 1e9:.2f} GB "
              f"alias={mem.alias_size_in_bytes / 1e9:.2f} GB")

    with spans.span("fedfog.setup.contract"):
        hlo = analyze_hlo(compiled.as_text())
        stats = hlo.collectives
        print(f"[train] collectives: {stats.count_by_kind} bytes="
              f"{ {k: f'{v:.2e}' for k, v in stats.bytes_by_kind.items()} }")
        for w in stats.trip_count_warnings[:3]:
            print(f"[train] note: {w}")

        # Raises on violation — holds on both the reference aggregation
        # and the sharded delta-pipeline kernel path (--pallas-agg). With
        # a fog tier on the kernel path the contract is per-tier (edge
        # psum + fog psum); the reference fog path is GSPMD-scheduled and
        # legally fuses back to the flat single all-reduce.
        contract_fog = fl_cfg.fog_nodes if fl_cfg.use_pallas_agg else 1
        _, delta_bytes = assert_inter_client_contract(
            hlo, rules, model.param_count(), fog_nodes=contract_fog
        )
    if rules.client_ways > 1:
        tiers = ("one delta all-reduce PER TIER (edge+fog)"
                 if contract_fog > 1 else "ONE inter-client all-reduce")
        print(f"[train] verified: {tiers} "
              f"({delta_bytes:.2e} B delta payload)")
    print(f"[train] phase map: {len(hlo.phases)} instructions in "
          f"{sorted(set(hlo.phases.values()))}")
    spans.register_program(hlo.module, hlo.phases, hlo.heads)
    return RoundProgram(compiled, hlo.phases, hlo.module)


if __name__ == "__main__":
    main()
