"""Paper-faithful edge simulation: the FedFog scenario of §IV end to end.

    PYTHONPATH=src python examples/edge_sim.py [--rounds 30] [--clients 48]

Reproduces the qualitative story of the paper's Figures 5-9 on the
EMNIST-like task: FedFog vs FogFaaS vs Random Client Selection, with data
drift injected mid-run and 10% label-flipping adversaries — printing
accuracy / latency / energy / cold-start traces per policy.

``--engine scan`` (default) runs each experiment as ONE compiled XLA
program (jax.lax.scan over rounds); ``--engine loop`` keeps the per-round
jitted loop for streaming/debugging; ``--engine async`` swaps in the
event-driven engine (repro.sim.events) — FedBuff-style buffered
aggregation on a continuous virtual clock, with a straggler tail and
client churn, printing the flush timeline instead of the round table.
``--sweep-seeds K`` additionally demos the sweep API: all K seeds of all
three policies vmapped/compiled per policy, reported as mean ± 95% CI.

``--population M`` scales the virtual client registry past the cohort:
scheduler/telemetry state is kept for all M clients while every round
samples a stratified ``--clients``-sized cohort, so per-round cost stays
cohort-sized (try ``--population 1000000``). ``--fog-nodes F`` engages
the hierarchical edge → fog → cloud reduction: the cohort is split into
F contiguous groups, each fog node computes partial Eq. 6 sums, and the
cloud combines them (requires ``aggregator=fedavg``; F must divide the
cohort). Both default to the flat dense setup, which they reproduce
bitwise.
"""
import argparse

from repro.fl.simulator import FedFogSimulator, SimulatorConfig
from repro.launch.compile_cache import use_persistent_cache
from repro.obs import MetricTap, NoopTracker, tracker_from_spec
from repro.sim.faults import FaultConfig

# Short spec keys for --faults (comma-separated k=v pairs; bare
# "failover" sets the flag): crash=0.2,retries=2,deadline=4000,quorum=0.5
_FAULT_KEYS = {
    "timeout": "timeout_rate",
    "crash": "crash_rate",
    "drop": "drop_rate",
    "corrupt": "corrupt_rate",
    "partition": "partition_rate",
    "outage": "fog_outage_rate",
    "failover": "fog_failover",
    "retries": "max_retries",
    "backoff": "backoff_base_ms",
    "deadline": "deadline_ms",
    "quorum": "quorum_frac",
}


def parse_faults(spec: str) -> FaultConfig | None:
    """``--faults`` spec → FaultConfig ('' → None → verbatim engines)."""
    if not spec:
        return None
    kw = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            if item != "failover":
                raise SystemExit(f"--faults: bad item {item!r} "
                                 f"(known: {', '.join(_FAULT_KEYS)})")
            kw["fog_failover"] = True
            continue
        k, v = item.split("=", 1)
        if k not in _FAULT_KEYS:
            raise SystemExit(f"--faults: unknown key {k!r} "
                             f"(known: {', '.join(_FAULT_KEYS)})")
        field = _FAULT_KEYS[k]
        kw[field] = int(v) if field == "max_retries" else float(v)
    return FaultConfig(**kw)


def _make_tap(tracker, args, channel: str, **const):
    """A tap when tracking is on, else None (tap-free trace)."""
    if isinstance(tracker, NoopTracker):
        return None  # no sink — keep the engines untapped
    return MetricTap(
        tracker, every=args.track_every, const=const, channel=channel
    )


def sweep_demo(args, tracker) -> None:
    """Sweep-API example: policies × seeds as compiled programs."""
    from repro.sim import run_sweep

    cfg = SimulatorConfig(
        task="emnist",
        num_clients=args.clients,
        rounds=args.rounds,
        top_k=args.topk,
        drift_period=args.rounds // 2,
        attack="label_flip",
        attack_fraction=0.1,
        population=args.population,
        fog_nodes=args.fog_nodes,
        faults=parse_faults(args.faults),
    )
    res = run_sweep(
        cfg,
        seeds=range(args.sweep_seeds),
        axes={"policy": ["fedfog", "fogfaas", "rcs"]},
        tracker=None if isinstance(tracker, NoopTracker) else tracker,
    )
    mean, ci = res.mean_ci("accuracy")
    print(f"\n=== sweep: final accuracy over {args.sweep_seeds} seeds ===")
    for g, ov in enumerate(res.configs):
        print(f"{ov['policy']:10s} {mean[g, -1]:.3f} ± {ci[g, -1]:.3f}")


def async_demo(args, tracker) -> None:
    """Event-driven engine: overlapping cohorts, staleness, churn."""
    from repro.sim.events import AsyncConfig, AsyncFedFogSimulator, ChurnConfig

    sim = AsyncFedFogSimulator(
        SimulatorConfig(
            task="emnist", num_clients=args.clients, rounds=args.rounds,
            top_k=args.topk, policy="fedfog", seed=0,
            population=args.population, fog_nodes=args.fog_nodes,
            faults=parse_faults(args.faults),
        ),
        AsyncConfig.fedbuff(
            max(2, args.topk // 2),
            dispatch_interval_ms=args.interval_ms,
            straggler_sigma=0.4,
            churn=ChurnConfig(arrival_rate=0.05, departure_rate=0.05),
        ),
        tap=_make_tap(tracker, args, "flush", engine="async"),
    )
    h = sim.run()
    print("=== async engine (FedBuff, straggler tail, churn) ===")
    print("virtual_t(ms) | accuracy | aggregated | staleness | energy(J)")
    step = max(1, h["num_flushes"] // 12)
    for f in range(0, h["num_flushes"], step):
        print(
            f"{h['t_ms'][f]:13.0f} | {h['accuracy'][f]:8.3f} "
            f"| {int(h['num_aggregated'][f]):10d} "
            f"| {h['mean_staleness'][f]:9.2f} | {h['energy_j'][f]:9.2f}"
        )
    print(
        f"\ndispatches={h['num_dispatches']} flushes={h['num_flushes']} "
        f"completions={h['num_completions']} "
        f"lost_to_churn={h['lost_inflight']} "
        f"final_acc={h['final_accuracy']:.3f} "
        f"virtual_time={h['virtual_time_ms'] / 1e3:.1f}s"
    )
    if args.faults:
        print(
            f"faults: failures={h['fault_failures']} "
            f"retries={h['fault_retries']} "
            f"terminal={h['fault_terminal']} "
            f"deadline_lost={h['fault_lost_deadline']} "
            f"corrupt={h['fault_corrupt']} "
            f"rounds_skipped={h['fault_skipped']}"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=48)
    ap.add_argument("--topk", type=int, default=16)
    ap.add_argument("--engine", choices=("scan", "loop", "async"),
                    default="scan")
    ap.add_argument("--interval-ms", type=float, default=1000.0,
                    help="async engine: virtual ms between dispatches")
    ap.add_argument("--sweep-seeds", type=int, default=0,
                    help="if >0, also run the multi-seed sweep demo")
    ap.add_argument("--population", type=int, default=None,
                    help="virtual client registry size M (>= --clients); "
                         "each round samples a stratified --clients-sized "
                         "cohort, so per-round cost stays cohort-sized "
                         "(default: dense, M == --clients)")
    ap.add_argument("--fog-nodes", type=int, default=1,
                    help="fog-tier width F of the edge->fog->cloud "
                         "reduction; F must divide --clients and needs "
                         "the fedavg aggregator (default 1 = flat, "
                         "bitwise identical to the pre-fog path)")
    ap.add_argument("--faults", default="",
                    help="fault-injection spec, e.g. "
                         "'crash=0.2,retries=2,deadline=8000,quorum=0.5' "
                         "(keys: timeout/crash/drop/corrupt/partition/"
                         "outage/failover/retries/backoff/deadline/"
                         "quorum; empty = faults off, engines verbatim)")
    ap.add_argument("--track", default="",
                    help="stream metrics to 'jsonl:PATH' / 'csv:PATH' "
                         "(comma-separate for multiple sinks); rounds "
                         "stream out of the compiled engines mid-run "
                         "via decimated io_callback taps (repro.obs)")
    ap.add_argument("--track-every", type=int, default=5,
                    help="tap decimation: emit every k-th round/flush")
    args = ap.parse_args()

    use_persistent_cache()
    tracker = tracker_from_spec(args.track)
    with tracker:
        _run(args, tracker)


def _run(args, tracker):
    if args.engine == "async":
        async_demo(args, tracker)
        if args.sweep_seeds > 0:
            sweep_demo(args, tracker)
        return

    results = {}
    for policy in ("fedfog", "fogfaas", "rcs"):
        sim = FedFogSimulator(
            SimulatorConfig(
                task="emnist",
                num_clients=args.clients,
                rounds=args.rounds,
                top_k=args.topk,
                policy=policy,
                drift_period=args.rounds // 2,
                attack="label_flip",
                attack_fraction=0.1,
                seed=0,
                population=args.population,
                fog_nodes=args.fog_nodes,
                faults=parse_faults(args.faults),
            ),
            tap=_make_tap(tracker, args, "round", policy=policy),
        )
        h = sim.run_scanned() if args.engine == "scan" else sim.run()
        results[policy] = h
        print(f"\n=== {policy} ===")
        print("round | accuracy | latency(ms) | energy(J) | cold starts")
        for r in range(0, args.rounds, max(1, args.rounds // 10)):
            print(
                f"{r:5d} | {h['accuracy'][r]:8.3f} | {h['round_latency_ms'][r]:11.0f}"
                f" | {h['energy_j'][r]:9.2f} | {int(h['cold_starts'][r]):4d}"
            )

    if args.faults:
        print("\n=== fault & recovery totals (per policy) ===")
        print(f"{'policy':10s} {'dispatched':>10s} {'completed':>9s} "
              f"{'terminal':>8s} {'lost':>5s} {'retries':>7s} "
              f"{'skipped':>7s}")
        for policy, h in results.items():
            print(
                f"{policy:10s} {int(sum(h['fault_dispatched'])):10d} "
                f"{int(sum(h['fault_completed'])):9d} "
                f"{int(sum(h['fault_terminal'])):8d} "
                f"{int(sum(h['fault_lost'])):5d} "
                f"{int(sum(h['fault_retries'])):7d} "
                f"{int(sum(h['round_skipped'])):7d}"
            )

    print("\n=== summary (paper Fig. 5 analogue) ===")
    print(f"{'policy':10s} {'final_acc':>9s} {'mean_lat_ms':>12s} "
          f"{'total_energy':>13s} {'cold_starts':>12s}")
    for policy, h in results.items():
        print(
            f"{policy:10s} {h['final_accuracy']:9.3f} "
            f"{h['mean_latency_ms']:12.0f} {h['total_energy_j']:13.1f} "
            f"{int(h['total_cold_starts']):12d}"
        )

    if args.sweep_seeds > 0:
        sweep_demo(args, tracker)


if __name__ == "__main__":
    main()
