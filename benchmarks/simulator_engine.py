"""Simulator engine throughput: seed-style Python loop vs scan-compiled
engine vs compile-once grouped sweep vs coalesced async engine.

The workload is the paper's actual benchmark shape — a NUMERIC config
grid (G learning rates) × S seeds × R rounds — run four ways:

  looped : the seed repo's engine — a fresh ``FedFogSimulator`` per
           (grid point, seed), one jitted dispatch per round, a
           ``float()`` host sync per metric per round, recompilation per
           simulator instance (G·S compiles).
  scanned: ``run_scanned()`` per seed on the base config — whole run in
           one ``lax.scan`` program, one device→host transfer per seed
           (continuity row: same shape as the historical baseline).
  sweep  : ``run_sweep()`` with structural/numeric grouping — the G grid
           points share ONE compiled program vmapped over (G, S); the
           row's derived fields split wall time into trace/compile/
           execute via the AOT ``jit(...).lower(...).compile()`` path.
  async  : ``run_sweep(engine="async")`` in the sync-equivalent cohort
           configuration — the event-driven engine (coalesced batched
           event stepping) doing the base config's work. Two explicitly
           named throughput columns: ``events_per_sec_exec`` is computed
           on EXECUTE time (compile attributed separately — the honest
           steady-state throughput of the event machinery) and
           ``events_per_sec_wall`` keeps the cold-wall definition of the
           pre-coalescing baselines (whose ``events_per_sec`` was
           wall-based) — compare each only against its own definition.

Two additional WARM-START rows measure the persistent compile cache
(``REPRO_COMPILE_CACHE_DIR``; unset, the checkout's ``.jax_cache/sweep``,
emptied before the cold rows):

  sweep_warm / async_events_warm : the same sweep/async workloads
           replayed after clearing the IN-PROCESS cache, so every
           executable comes back through disk deserialization — the
           cost a second process running the same grid pays
           (``n_compiles=0``, wall → exec). ``REPRO_BENCH_WARM=1``
           emits ONLY these rows (no cold engines), which is how
           scripts/ci.sh's second pass asserts a fresh process actually
           warm-starts from the first pass's cache.

Wall-clock per row still includes compilation — that is the honest
end-to-end cost a cold benchmark suite pays; the compile_s/exec_s split
shows where it goes, and the compile-once cache is exactly what the
sweep row amortizes across the grid. JAX's own persistent cache
(``repro.launch.compile_cache``) may still serve XLA compiles left by an
earlier run; scripts/ci.sh points it at an emptied directory so its cold
rows compile from nothing. Also reports the max absolute
accuracy-history deviation between engines as a correctness cross-check.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import Row, SCALE, fmt, preset
from repro.fl.simulator import FedFogSimulator, SimulatorConfig
from repro.launch.compile_cache import SWEEP_DIR
from repro.sim import clear_compile_cache, run_sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_SEEDS = {"quick": 2, "default": 4, "full": 8}
# Numeric grid: G points that share one structural signature, so the
# grouped sweep compiles ONCE while the naive loop re-traces per point.
LR_GRID = {"quick": [0.03, 0.04, 0.05, 0.06],
           "default": [0.03, 0.04, 0.05, 0.06],
           "full": [0.02, 0.03, 0.04, 0.05, 0.06]}


def run() -> list[Row]:
    p = preset()
    n_seeds = N_SEEDS[SCALE]
    rounds = p["rounds"]
    lrs = LR_GRID[SCALE]
    g = len(lrs)
    base = SimulatorConfig(
        task="emnist", num_clients=p["clients"], rounds=rounds, top_k=p["topk"]
    )
    base_rounds = n_seeds * rounds  # single-config sim-rounds
    grid_rounds = g * base_rounds  # grid-workload sim-rounds

    # Persistent warm-start cache: the caller's directory, else the
    # checkout's fixed one, emptied before a cold pass so the cold rows
    # are cold (a warm-only pass reads what the last cold pass left).
    # The variable is put back afterwards, so later suites in the same
    # harness process do not serialize their sweeps.
    owned = not os.environ.get("REPRO_COMPILE_CACHE_DIR")
    if owned:
        os.environ["REPRO_COMPILE_CACHE_DIR"] = SWEEP_DIR
    try:
        if os.environ.get("REPRO_BENCH_WARM", "0") == "1":
            return _warm_rows(base, lrs, n_seeds, rounds, p, grid_rounds)
        if owned:
            shutil.rmtree(SWEEP_DIR, ignore_errors=True)
        return _cold_and_warm_rows(base, lrs, n_seeds, rounds, p,
                                   grid_rounds, g)
    finally:
        if owned:
            os.environ.pop("REPRO_COMPILE_CACHE_DIR", None)


def _cold_and_warm_rows(
    base, lrs, n_seeds, rounds, p, grid_rounds, g
) -> list[Row]:
    import dataclasses

    base_rounds = n_seeds * rounds  # single-config sim-rounds

    # --- seed-style Python loop over the grid (fresh sim per run) ------ #
    t0 = time.time()
    looped = [
        [
            FedFogSimulator(
                dataclasses.replace(base, lr=lr, seed=s)
            ).run(rounds)
            for s in range(n_seeds)
        ]
        for lr in lrs
    ]
    t_loop = time.time() - t0

    # --- scan-compiled engine, one sim per seed (base config only) ----- #
    # AOT-compile the scan program ONCE and execute it per seed: the jit
    # dispatch caches are per-instance, so the old per-seed run_scanned()
    # loop recompiled for every simulator and the row's "speedup" mixed a
    # one-off compile into every per-round number (the recorded
    # scanned_speedup_vs_loop=0.82 artifact). compile_s / exec_s are now
    # attributed separately and the summary compares execute-to-execute.
    t0 = time.time()
    scan_exe = FedFogSimulator(
        dataclasses.replace(base, seed=0)
    ).aot_scanned(rounds)
    t_scan_compile = time.time() - t0
    t0 = time.time()
    scanned = [
        FedFogSimulator(dataclasses.replace(base, seed=s)).run_scanned_with(
            scan_exe, rounds
        )
        for s in range(n_seeds)
    ]
    t_scan_exec = time.time() - t0
    t_scan = t_scan_compile + t_scan_exec

    # --- grouped sweep: the whole grid × seed batch as ONE program ----- #
    tm: dict = {}
    t0 = time.time()
    res = run_sweep(
        base, seeds=range(n_seeds), axes={"lr": lrs}, rounds=rounds,
        timings=tm,
    )
    t_sweep = time.time() - t0

    # --- fault tax: the same grid through the ACTIVE fault gate -------- #
    # Rates are lifted numerics, so the faulted grid still compiles ONCE;
    # the row prices what the gated program (retry chains, counters,
    # quorum select) adds per sim-round over the fault-free sweep row.
    from repro.sim.faults import FaultConfig

    tm_f: dict = {}
    t0 = time.time()
    res_fault = run_sweep(
        base, seeds=range(n_seeds),
        cases=[
            {"lr": lr, "faults": FaultConfig(crash_rate=0.25, max_retries=1)}
            for lr in lrs
        ],
        rounds=rounds, timings=tm_f,
    )
    t_fault = time.time() - t0
    fault_retries = int(np.asarray(res_fault.history["fault_retries"]).sum())

    # --- event-driven engine, sync-equivalent cohort config ------------ #
    from repro.sim.events import AsyncConfig

    tm_a: dict = {}
    t0 = time.time()
    res_async = run_sweep(
        base, seeds=range(n_seeds), rounds=rounds,
        engine="async", async_cfg=AsyncConfig(staleness_exponent=0.0),
        timings=tm_a,
    )
    t_async = time.time() - t0
    # one dispatch + its completions + the flush ≈ (topk+2) events/round
    sim_events = int((res_async.metric("valid") > 0).sum()) + n_seeds * rounds * (
        p["topk"] + 1
    )
    ev_exec = sim_events / max(tm_a.get("exec_s", 0.0), 1e-9)
    ev_wall = sim_events / max(t_async, 1e-9)

    # correctness cross-check: all four engines tell the same story.
    # scanned/async run the BASE config, so its lr must be a grid point
    # or the deviation columns would compare different learning rates.
    assert base.lr in lrs, f"LR_GRID[{SCALE}] must contain base lr {base.lr}"
    acc_loop = np.asarray([[h["accuracy"] for h in seeds] for seeds in looped])
    base_g = lrs.index(base.lr)
    acc_scan = np.asarray([h["accuracy"] for h in scanned])
    acc_sweep = np.asarray(res.metric("accuracy"))
    acc_async = np.asarray(res_async.metric("accuracy")[0])[:, :rounds]
    dev_scan = float(np.abs(acc_loop[base_g] - acc_scan).max())
    dev_sweep = float(np.abs(acc_loop - acc_sweep).max())
    dev_async = float(np.abs(acc_loop[base_g] - acc_async).max())

    warm_rows = _warm_rows(
        base, lrs, n_seeds, rounds, p, grid_rounds,
        cold_acc=acc_sweep, cold_acc_async=np.asarray(
            res_async.metric("accuracy")
        ),
    ) + [
        _tracked_row(base, rounds, p, t_scan_exec / base_rounds * 1e6,
                     acc_scan),
        _sharded_row(lrs, rounds, p), _population_row(p),
    ]

    shape = fmt(grid=g, seeds=n_seeds, rounds=rounds, clients=p["clients"])
    return [
        Row(
            "simulator_engine/looped",
            t_loop / grid_rounds * 1e6,
            f"wall_s={t_loop:.2f};{shape}",
        ),
        Row(
            "simulator_engine/scanned",
            t_scan / base_rounds * 1e6,
            f"wall_s={t_scan:.2f};"
            f"compile_s={t_scan_compile:.2f};"
            f"exec_s={t_scan_exec:.2f};"
            f"max_acc_dev={dev_scan:.2g};"
            + fmt(seeds=n_seeds, rounds=rounds, clients=p["clients"]),
        ),
        Row(
            "simulator_engine/sweep",
            t_sweep / grid_rounds * 1e6,
            f"wall_s={t_sweep:.2f};"
            f"trace_s={tm.get('trace_s', 0.0):.2f};"
            f"compile_s={tm.get('compile_s', 0.0):.2f};"
            f"exec_s={tm.get('exec_s', 0.0):.2f};"
            f"n_compiles={tm.get('n_compiles', 0)};"
            f"cache_hits={tm.get('cache_hits', 0)};"
            f"max_acc_dev={dev_sweep:.2g};{shape}",
        ),
        Row(
            "simulator_engine/sweep_faulted",
            t_fault / grid_rounds * 1e6,
            f"wall_s={t_fault:.2f};"
            f"compile_s={tm_f.get('compile_s', 0.0):.2f};"
            f"exec_s={tm_f.get('exec_s', 0.0):.2f};"
            f"n_compiles={tm_f.get('n_compiles', 0)};"
            f"fault_tax={t_fault / max(t_sweep, 1e-9):.3f};"
            f"total_retries={fault_retries};{shape}",
        ),
        Row(
            "simulator_engine/async_events",
            t_async / base_rounds * 1e6,
            f"wall_s={t_async:.2f};"
            f"trace_s={tm_a.get('trace_s', 0.0):.2f};"
            f"compile_s={tm_a.get('compile_s', 0.0):.2f};"
            f"exec_s={tm_a.get('exec_s', 0.0):.2f};"
            f"max_acc_dev={dev_async:.2g};"
            f"events_per_sec_exec={ev_exec:.0f};"
            f"events_per_sec_wall={ev_wall:.1f};"
            + fmt(seeds=n_seeds, rounds=rounds, clients=p["clients"]),
        ),
        Row(
            "simulator_engine/summary",
            0.0,
            fmt(
                # per-sim-round ratios: the rows cover different workloads
                # (loop+sweep run the G-point grid, scanned+async the base
                # config), so raw wall ratios would not be like-for-like.
                # scanned speedup is EXECUTE-to-execute (the scan program
                # compiles once; folding that one-off into every per-round
                # number was the 0.82 artifact); _wall keeps the old
                # cold-wall definition for trend continuity.
                scanned_speedup_vs_loop=(t_loop / grid_rounds)
                / max(t_scan_exec / base_rounds, 1e-9),
                scanned_speedup_vs_loop_wall=(t_loop / grid_rounds)
                / max(t_scan / base_rounds, 1e-9),
                sweep_speedup_vs_loop=t_loop / max(t_sweep, 1e-9),
                async_overhead_vs_sweep=(t_async / base_rounds)
                / max(t_sweep / grid_rounds, 1e-9),
                # _exec = steady-state event throughput (compile is
                # attributed separately); _wall keeps the historical
                # cold-wall definition (the pre-coalescing baselines'
                # `events_per_sec` was wall-based) — never compare one
                # against the other.
                events_per_sec_exec=ev_exec,
                events_per_sec_wall=ev_wall,
            ),
        ),
    ] + warm_rows


def _tracked_row(base, rounds, p, scanned_exec_us, acc_scan) -> Row:
    """``scanned_tracked``: the scan engine with a live metric tap
    (JsonlTracker sink, decimation 10) — the observability tax. The tap
    is an ordered io_callback under a ``step % 10 == 0`` cond inside the
    compiled scan, so the WARM per-round cost must stay within a few
    percent of the untapped scanned row's execute time
    (``tracked_over_scanned_exec``; the <10% acceptance gate). The first
    call's compile is attributed separately, and the tapped history must
    match the untapped engine bitwise (``max_acc_dev``)."""
    import dataclasses

    from repro.obs import JsonlTracker, MetricTap

    import tempfile as _tf

    every = 10
    path = os.path.join(_tf.mkdtemp(prefix="repro-bench-track-"),
                        "rows.jsonl")
    with JsonlTracker(path) as tracker:
        tap = MetricTap(tracker, every=every, channel="round")
        sim = FedFogSimulator(
            dataclasses.replace(base, seed=0), tap=tap
        )
        t0 = time.time()
        h = sim.run_scanned(rounds)  # cold: traces + compiles the tap
        t_cold = time.time() - t0
        t0 = time.time()
        sim.run_scanned(rounds)  # warm: jit cache hit, exec + taps only
        t_warm = time.time() - t0
    dev = float(np.abs(np.asarray(h["accuracy"])
                       - np.asarray(acc_scan[0])).max())
    rows_streamed = sum(1 for _ in open(path))
    warm_us = t_warm / rounds * 1e6
    return Row(
        "simulator_engine/scanned_tracked",
        warm_us,
        f"wall_cold_s={t_cold:.2f};"
        f"tracked_over_scanned_exec="
        f"{warm_us / max(scanned_exec_us, 1e-9):.3f};"
        f"max_acc_dev={dev:.2g};"
        f"rows_streamed={rows_streamed};"
        + fmt(every=every, rounds=rounds, clients=p["clients"]),
    )


def _sharded_row(lrs, rounds, p) -> Row:
    """``sweep_sharded``: the grouped lr-grid sweep with its seed batch
    sharded across 8 fake CPU devices (``run_sweep(devices=8)``), via a
    subprocess worker (the fake-device flag must precede jax init). One
    seed per device, so the executable's seed axis is fully parallel.
    The worker is pinned to the CPU, so the row is a CPU-host row."""
    n_seeds = 8
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (
        os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    # the worker measures its own cold compile — don't warm-start it from
    # this process's persistent cache dir
    env.pop("REPRO_COMPILE_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.sweep_sharded_worker",
         "--devices", "8", "--seeds", str(n_seeds),
         "--clients", str(p["clients"]), "--rounds", str(rounds),
         "--topk", str(p["topk"]), "--lrs", ",".join(map(str, lrs))],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sweep_sharded worker rc={proc.returncode}: {proc.stderr[-500:]}"
        )
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    return Row(
        "simulator_engine/sweep_sharded",
        res["wall_s"] / res["sim_rounds"] * 1e6,
        f"wall_s={res['wall_s']:.2f};"
        f"compile_s={res['compile_s']:.2f};"
        f"exec_s={res['exec_s']:.2f};"
        f"acc_mean={res['acc_mean']:.4g};"
        + fmt(platform="cpu", devices=res["devices"], grid=len(lrs),
              seeds=n_seeds,
              rounds=rounds, clients=p["clients"]),
    )


def _peak_mem_mb(compiled) -> float | None:
    """Best-effort peak-HBM estimate from the AOT executable's
    ``memory_analysis()`` (argument + output + temp + generated code);
    None when the backend doesn't implement it."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    total = 0.0
    found = False
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            total += float(v)
            found = True
    return round(total / 2**20, 1) if found else None


def _population_row(p) -> Row:
    """``simulator_engine/population``: the ISSUE 7 acceptance row — a
    1M-virtual-client population sampled down to a 64-client cohort per
    round must cost ~what today's dense 64-client run costs (the per-
    round work is cohort-sized; only O(M) telemetry/scheduler gathers
    and scatters see the population). Cohort/population are FIXED at
    64/1M across bench scales so the ratio is comparable everywhere;
    rounds follow the preset (capped) to bound wall time. Columns carry
    both us/round numbers, their ratio, the AOT executables' peak-memory
    estimates, and — attributed separately, like compile — the one-time
    state-build cost a fresh same-config instance pays (``init_ms``: the
    (M,) registries through the shared jitted init)."""
    import dataclasses

    cohort, population = 64, 1_000_000
    rounds = min(p["rounds"], 8)
    dense = SimulatorConfig(
        task="emnist", num_clients=cohort, rounds=rounds, top_k=p["topk"]
    )
    pop = dataclasses.replace(dense, population=population)

    def prepare(cfg):
        sim = FedFogSimulator(cfg)
        t0 = time.time()
        exe = sim.aot_scanned(rounds)
        compile_s = time.time() - t0
        h = sim.run_scanned_with(exe, rounds)  # warm (first dispatch);
        # also the accuracy sample — later reps advance the carried state.
        # One-time state build (the (M,) registries in population mode)
        # is attributed separately, like compile: a fresh same-config
        # instance reuses the shared jitted init executable.
        t0 = time.time()
        fresh = FedFogSimulator(cfg)
        jax.block_until_ready((fresh.env, fresh.telemetry))
        init_ms = (time.time() - t0) * 1e3
        return sim, exe, compile_s, init_ms, _peak_mem_mb(exe), h

    def timed(sim, exe):
        t0 = time.time()
        sim.run_scanned_with(exe, rounds)
        return (time.time() - t0) / rounds * 1e6

    d_sim, d_exe, dense_compile, dense_init, dense_mem, _ = prepare(dense)
    p_sim, p_exe, pop_compile, pop_init, pop_mem, h_pop = prepare(pop)
    # The ratio below is an acceptance gate; single runs on a shared
    # host jitter ±20% and conditions drift over the suite. Interleave
    # the reps so both configs see the same machine state, take best-of.
    dense_us = pop_us = float("inf")
    for _ in range(3):
        dense_us = min(dense_us, timed(d_sim, d_exe))
        pop_us = min(pop_us, timed(p_sim, p_exe))
    return Row(
        "simulator_engine/population",
        pop_us,
        fmt(
            dense_us_per_round=dense_us,
            pop_over_dense=pop_us / max(dense_us, 1e-9),
            peak_mem_mb=pop_mem if pop_mem is not None else "na",
            dense_peak_mem_mb=dense_mem if dense_mem is not None else "na",
            compile_s=pop_compile,
            dense_compile_s=dense_compile,
            init_ms=pop_init,
            dense_init_ms=dense_init,
            final_acc=float(h_pop["accuracy"][-1]),
            population=population,
            cohort=cohort,
            rounds=rounds,
        ),
    )


def _warm_rows(
    base, lrs, n_seeds, rounds, p, grid_rounds,
    cold_acc=None, cold_acc_async=None,
) -> list[Row]:
    """Warm-start rows: replay the sweep + async workloads through the
    persistent compile cache (in-process cache cleared first, so every
    executable deserializes from REPRO_COMPILE_CACHE_DIR — the cost a
    SECOND process running the same grid pays)."""
    from repro.sim.events import AsyncConfig

    base_rounds = n_seeds * rounds

    clear_compile_cache()
    tm: dict = {}
    t0 = time.time()
    res = run_sweep(
        base, seeds=range(n_seeds), axes={"lr": lrs}, rounds=rounds,
        timings=tm,
    )
    t_sweep = time.time() - t0

    clear_compile_cache()
    tm_a: dict = {}
    t0 = time.time()
    res_a = run_sweep(
        base, seeds=range(n_seeds), rounds=rounds,
        engine="async", async_cfg=AsyncConfig(staleness_exponent=0.0),
        timings=tm_a,
    )
    t_async = time.time() - t0
    sim_events = int((res_a.metric("valid") > 0).sum()) + n_seeds * rounds * (
        p["topk"] + 1
    )
    ev_exec = sim_events / max(tm_a.get("exec_s", 0.0), 1e-9)
    ev_wall = sim_events / max(t_async, 1e-9)

    # replaying a serialized executable is exact — flag any drift
    dev = dev_a = ""
    if cold_acc is not None:
        d = float(np.abs(np.asarray(res.metric("accuracy")) - cold_acc).max())
        dev = f"max_acc_dev={d:.2g};"
    if cold_acc_async is not None:
        d = float(
            np.abs(np.asarray(res_a.metric("accuracy")) - cold_acc_async).max()
        )
        dev_a = f"max_acc_dev={d:.2g};"

    return [
        Row(
            "simulator_engine/sweep_warm",
            t_sweep / grid_rounds * 1e6,
            f"wall_s={t_sweep:.2f};"
            f"load_s={tm.get('load_s', 0.0):.2f};"
            f"exec_s={tm.get('exec_s', 0.0):.2f};"
            f"n_compiles={tm.get('n_compiles', 0)};"
            f"disk_hits={tm.get('disk_hits', 0)};{dev}"
            + fmt(grid=len(lrs), seeds=n_seeds, rounds=rounds,
                  clients=p["clients"]),
        ),
        Row(
            "simulator_engine/async_events_warm",
            t_async / base_rounds * 1e6,
            f"wall_s={t_async:.2f};"
            f"load_s={tm_a.get('load_s', 0.0):.2f};"
            f"exec_s={tm_a.get('exec_s', 0.0):.2f};"
            f"n_compiles={tm_a.get('n_compiles', 0)};"
            f"disk_hits={tm_a.get('disk_hits', 0)};{dev_a}"
            f"events_per_sec_exec={ev_exec:.0f};"
            f"events_per_sec_wall={ev_wall:.1f};"
            + fmt(seeds=n_seeds, rounds=rounds, clients=p["clients"]),
        ),
    ]
