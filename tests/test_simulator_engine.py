"""Scan-compiled engine, vmapped sweep, and shared DES cost model.

Covers the three contracts the simulation-stack refactor must hold:
  (a) ``run_scanned()`` reproduces the per-round loop for all policies;
  (b) sweeps are seed-deterministic and seed s of a sweep reproduces a
      standalone ``run_scanned()`` at seed s;
  (c) the shared ``RoundCostModel`` reproduces the seed repo's
      latency/energy formulas consumed by both engines.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.telemetry import TelemetryConfig, make_profiles
from repro.fl.simulator import FedFogSimulator, SimulatorConfig
from repro.sim import (
    FaasSimConfig,
    RoundCostModel,
    round_energy_j,
    round_times_ms,
    run_sweep,
)

POLICIES = ("fedfog", "rcs", "fogfaas", "vanilla")


def _cfg(**kw) -> SimulatorConfig:
    base = dict(
        task="emnist", num_clients=8, rounds=4, top_k=4, hidden=(16,), seed=0
    )
    base.update(kw)
    return SimulatorConfig(**base)


# --------------------------------------------------------------------- #
# (a) scanned engine ≡ per-round loop
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("policy", POLICIES)
def test_run_scanned_matches_loop(policy):
    cfg = _cfg(policy=policy)
    h_loop = FedFogSimulator(cfg).run()
    h_scan = FedFogSimulator(cfg).run_scanned()
    assert set(h_loop) == set(h_scan)
    for name in h_loop:
        np.testing.assert_allclose(
            np.asarray(h_loop[name]),
            np.asarray(h_scan[name]),
            rtol=1e-5,
            atol=1e-5,
            err_msg=f"{policy}/{name}",
        )


def test_run_scanned_advances_state_like_loop():
    cfg = _cfg()
    a, b = FedFogSimulator(cfg), FedFogSimulator(cfg)
    a.run()
    b.run_scanned()
    for pa, pb in zip(
        jnp.ravel(a.params[0]["w"])[:32], jnp.ravel(b.params[0]["w"])[:32]
    ):
        np.testing.assert_allclose(float(pa), float(pb), rtol=1e-5, atol=1e-6)
    assert int(a.sched_state.round_index) == int(b.sched_state.round_index) == 4


# --------------------------------------------------------------------- #
# (b) sweep: deterministic, and seed-sliced ≡ standalone runs
# --------------------------------------------------------------------- #
def test_sweep_is_seed_deterministic():
    cfg = _cfg()
    r1 = run_sweep(cfg, seeds=[0, 1], axes={"policy": ["fedfog", "rcs"]})
    r2 = run_sweep(cfg, seeds=[0, 1], axes={"policy": ["fedfog", "rcs"]})
    assert r1.configs == r2.configs
    for name in r1.history:
        np.testing.assert_array_equal(r1.history[name], r2.history[name])
    # different seeds genuinely differ
    assert not np.array_equal(
        r1.metric("accuracy")[:, 0], r1.metric("accuracy")[:, 1]
    )


def test_sweep_matches_standalone_scanned_runs():
    cfg = _cfg()
    seeds = [0, 3]
    res = run_sweep(cfg, seeds=seeds, cases=[{"policy": "fedfog"}, {"top_k": 2}])
    assert res.metric("accuracy").shape == (2, 2, cfg.rounds)
    for g, overrides in enumerate(res.configs):
        for si, s in enumerate(seeds):
            h = FedFogSimulator(
                dataclasses.replace(cfg, seed=s, **overrides)
            ).run_scanned()
            for name in ("accuracy", "round_latency_ms", "energy_j",
                         "cold_starts", "num_selected"):
                np.testing.assert_allclose(
                    res.metric(name)[g, si],
                    np.asarray(h[name]),
                    rtol=1e-5,
                    atol=1e-5,
                    err_msg=f"{overrides}/seed{s}/{name}",
                )


def test_sweep_devices_sharding_bit_identical():
    """run_sweep(devices=N) — including the seed-padding path where
    |seeds| is not a multiple of N — reproduces the single-device sweep
    bit-for-bit. Subprocess: the fake-device count must be set before
    jax initializes."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = """
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)
import numpy as np
from repro.fl.simulator import SimulatorConfig
from repro.sim import run_sweep

cfg = SimulatorConfig(task="emnist", num_clients=8, rounds=3, top_k=4,
                      hidden=(16,), seed=0)
for seeds in ([0, 1, 2], [0, 1, 2, 3], [0, 1, 2, 3, 4, 5]):
    a = run_sweep(cfg, seeds=seeds)
    b = run_sweep(cfg, seeds=seeds, devices=4)
    for k in a.history:
        assert np.array_equal(a.history[k], b.history[k]), (len(seeds), k)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=repo, timeout=600,
    )
    assert proc.returncode == 0 and "OK" in proc.stdout, (
        proc.stdout[-1000:], proc.stderr[-1000:]
    )


def test_grouped_sweep_bitwise_matches_per_point():
    """Compile-once grouping is a pure execution-strategy change: on a
    mixed structural×numeric grid (policies change the trace; lr / top_k
    / Eq. 3 thresholds are lifted to vmapped data) the grouped sweep must
    reproduce the per-grid-point sweep BITWISE."""
    from repro.core.scheduler import SchedulerConfig

    cfg = _cfg(rounds=3)
    cases = [
        {"policy": "fedfog", "lr": 0.03},
        {"policy": "fedfog", "lr": 0.07},
        {"policy": "fedfog", "lr": 0.03, "top_k": 2},
        {"policy": "rcs", "lr": 0.05},
        {"scheduler": SchedulerConfig(theta_h=0.5, theta_e=0.4)},
        {"scheduler": SchedulerConfig(theta_h=0.7, theta_e=0.6)},
    ]
    from repro.sim import clear_compile_cache

    clear_compile_cache()  # count this call's compiles, not stale hits
    tm: dict = {}
    grouped = run_sweep(cfg, seeds=[0, 1], cases=cases, timings=tm)
    per_point = run_sweep(cfg, seeds=[0, 1], cases=cases, group=False)
    # fedfog cases (lr/top_k/theta lifted) collapse into one group, rcs
    # into another — strictly fewer compiled programs than grid points
    assert tm["n_compiles"] < len(cases)
    assert tm["cache_hits"] == 0
    assert grouped.configs == per_point.configs
    for name in grouped.history:
        np.testing.assert_array_equal(
            grouped.history[name], per_point.history[name], err_msg=name
        )


def test_sweep_compile_cache_reuse():
    """A structurally-identical second sweep replays cached executables:
    zero new compiles, bit-identical histories."""
    from repro.sim import clear_compile_cache

    cfg = _cfg(rounds=3)
    axes = {"lr": [0.02, 0.05, 0.08]}
    clear_compile_cache()
    tm1: dict = {}
    r1 = run_sweep(cfg, seeds=[0, 1], axes=axes, timings=tm1)
    tm2: dict = {}
    r2 = run_sweep(cfg, seeds=[0, 1], axes=axes, timings=tm2)
    assert tm1["n_compiles"] == 1  # one structural group for the lr grid
    assert tm2["n_compiles"] == 0 and tm2["cache_hits"] == 1
    assert tm2["compile_s"] == 0.0
    for name in r1.history:
        np.testing.assert_array_equal(r1.history[name], r2.history[name])


def test_aot_scanned_matches_run_scanned():
    """aot_scanned + run_scanned_with reproduce run_scanned bitwise —
    including on a DIFFERENT same-shape simulator instance (the sharing
    that lets benchmarks compile the scan program once per seed sweep)."""
    cfg = _cfg(rounds=3)
    exe = FedFogSimulator(cfg).aot_scanned()
    for s in range(2):
        c = dataclasses.replace(cfg, seed=s)
        a = FedFogSimulator(c).run_scanned()
        b = FedFogSimulator(c).run_scanned_with(exe)
        assert set(a) == set(b)
        for name in a:
            np.testing.assert_array_equal(
                np.asarray(a[name]), np.asarray(b[name]), err_msg=name
            )


@pytest.mark.parametrize("population", [None, 32])
def test_scan_carry_leaves_own_their_buffers(population):
    """The scanned engine donates params/scheduler/telemetry on an
    accelerator; one buffer behind two leaves cannot be donated twice
    (on a TPU the first scanned run fails), so every leaf owns its own."""
    import jax

    sim = FedFogSimulator(_cfg(population=population))
    leaves = jax.tree.leaves((sim.params, sim.sched_state, sim.telemetry))
    ptrs = [x.unsafe_buffer_pointer() for x in leaves]
    assert len(set(ptrs)) == len(ptrs)


def test_sweep_signature_aggregator_structural_trim_lifted():
    """Compile-cache keys must distinguish the kernel gate STRUCTURALLY:
    ``aggregator`` and ``use_pallas_agg`` each open a new compile group,
    while ``trim_fraction`` is numeric data lifted into the vmapped
    batch — two trim fractions share one executable. Grouped results
    stay bitwise-equal to the per-point sweep."""
    from repro.sim import clear_compile_cache

    cfg = _cfg(rounds=2)
    cases = [
        {"aggregator": "trimmed", "trim_fraction": 0.1},
        {"aggregator": "trimmed", "trim_fraction": 0.2},  # same group
        {"aggregator": "median"},  # new structural group
        {"use_pallas_agg": True},  # kernel routing is structural too
    ]
    clear_compile_cache()
    tm: dict = {}
    grouped = run_sweep(cfg, seeds=[0], cases=cases, timings=tm)
    assert tm["n_compiles"] == 3, tm  # trimmed×2 collapse into one
    per_point = run_sweep(cfg, seeds=[0], cases=cases, group=False)
    assert grouped.configs == per_point.configs
    for name in grouped.history:
        np.testing.assert_array_equal(
            grouped.history[name], per_point.history[name], err_msg=name
        )


def test_round_pallas_agg_matches_reference():
    """use_pallas_agg routes Eq. 6 + server apply through the fused
    kernel (interpret mode on CPU); a full multi-round run must agree
    with the reference fedavg_stacked path to float tolerance, and the
    kernel itself must agree with fedavg_apply_ref on round-shaped
    inputs."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fedavg import fedavg_apply, fedavg_apply_ref

    cfg = _cfg(rounds=3)
    h_ref = FedFogSimulator(cfg).run_scanned()
    h_pal = FedFogSimulator(
        dataclasses.replace(cfg, use_pallas_agg=True)
    ).run_scanned()
    for name in h_ref:
        np.testing.assert_allclose(
            np.asarray(h_ref[name]), np.asarray(h_pal[name]),
            rtol=1e-5, atol=1e-5, err_msg=name,
        )
    # direct kernel-vs-oracle cross-check at simulator shapes
    key = jax.random.PRNGKey(3)
    upd = jax.random.normal(key, (cfg.num_clients, 16 * 62))
    base = jax.random.normal(jax.random.fold_in(key, 1), (16 * 62,))
    mask = jnp.arange(cfg.num_clients) < 4
    sizes = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2),
                                      (cfg.num_clients,))) * 100
    out = fedavg_apply(upd, base, mask, sizes, lr=0.7)
    ref = fedavg_apply_ref(upd, base, mask, sizes, lr=0.7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_sweep_reductions_shapes():
    cfg = _cfg(rounds=3)
    res = run_sweep(cfg, seeds=[0, 1, 2])
    mean, ci = res.mean_ci("accuracy")
    assert mean.shape == ci.shape == (1, 3)
    m, s = res.mean_std("energy_j", reduce="sum")
    assert m.shape == s.shape == (1,)
    stats = res.stats(0)
    assert stats["final_accuracy"].shape == (3,)
    np.testing.assert_allclose(
        stats["total_energy_j"], res.metric("energy_j")[0].sum(axis=-1)
    )


# --------------------------------------------------------------------- #
# (c) shared cost model reproduces the seed formulas for both engines
# --------------------------------------------------------------------- #
def _fixture(n=16, seed=0):
    prof = make_profiles(TelemetryConfig(num_clients=n, seed=seed))
    rng = np.random.RandomState(seed)
    selected = jnp.asarray(rng.rand(n) < 0.6)
    warm = jnp.asarray(rng.rand(n) < 0.5)
    return prof, selected, warm


def test_cost_model_times_reproduce_seed_formula():
    cfg = FaasSimConfig()
    prof, selected, warm = _fixture()
    n = selected.shape[0]
    workload, up, down = 1e9, 1e6, 2e6
    for policy in ("fedfog", "fogfaas"):
        per, rnd, orch = round_times_ms(
            cfg, prof, selected, warm, workload, up, down, policy=policy
        )
        # seed formula, per-client orchestration share included
        k = float(jnp.sum(selected))
        t_comp = workload / prof.mips * 1e3
        t_net = (up / prof.bw_up + down / prof.bw_down) * 1e3 + prof.rtt_ms
        delta = jnp.where(
            warm, cfg.cold_start.delta_warm_ms, cfg.cold_start.delta_cold_ms
        )
        if policy == "fedfog":
            orch_ref = cfg.sort_ms_per_nlogn * n * np.log2(n) + cfg.dispatch_ms * k
        else:
            orch_ref = cfg.deploy_ms * n + cfg.poll_ms * n * n
        per_ref = (delta + t_comp + t_net + orch_ref / max(k, 1.0)) * selected
        np.testing.assert_allclose(np.asarray(per), np.asarray(per_ref), rtol=1e-5)
        np.testing.assert_allclose(float(orch), float(orch_ref), rtol=1e-5)
        np.testing.assert_allclose(
            float(rnd), float(np.asarray(per_ref).max()), rtol=1e-5
        )


def test_per_client_latency_masked_for_unselected():
    cfg = FaasSimConfig()
    prof, selected, warm = _fixture()
    per, _, _ = round_times_ms(cfg, prof, selected, warm, 1e9, 1e6, 2e6)
    np.testing.assert_array_equal(
        np.asarray(per)[~np.asarray(selected)], 0.0
    )
    assert (np.asarray(per)[np.asarray(selected)] > 0).all()


def test_cost_model_energy_reproduces_both_engine_formulas():
    cfg = FaasSimConfig()
    prof, selected, warm = _fixture()
    workload, up = 1e9, 1e6
    e = RoundCostModel(cfg).energy_j(selected, warm, workload, up)
    # paper-scale engine formula (seed sim/faas.py)
    e_faas = round_energy_j(cfg, prof, selected, warm, workload, up)
    np.testing.assert_allclose(np.asarray(e), np.asarray(e_faas), rtol=1e-6)
    # pod-scale engine formula (seed fl/round.py inline expression)
    em = cfg.energy
    sel_f = np.asarray(selected, np.float32)
    e_pod = sel_f * (em.c_cpu * workload + em.c_tx * up) + (
        np.asarray(selected) & ~np.asarray(warm)
    ) * em.cold_start_energy_j
    np.testing.assert_allclose(np.asarray(e), e_pod, rtol=1e-6)


def test_round_costs_bundle_consistency():
    cfg = FaasSimConfig()
    prof, selected, warm = _fixture()
    costs = RoundCostModel(cfg).round_costs(
        prof, selected, warm, 1e9, 1e6, 2e6, policy="fedfog"
    )
    per, rnd, orch = round_times_ms(cfg, prof, selected, warm, 1e9, 1e6, 2e6)
    np.testing.assert_allclose(np.asarray(costs.per_client_ms), np.asarray(per))
    np.testing.assert_allclose(float(costs.round_ms), float(rnd))
    np.testing.assert_allclose(float(costs.orchestration_ms), float(orch))
    assert int(costs.cold_starts) == int(
        np.sum(np.asarray(selected) & ~np.asarray(warm))
    )


def test_cost_model_from_scheduler_matches_faas_defaults():
    from repro.core.scheduler import SchedulerConfig

    m = RoundCostModel.from_scheduler(SchedulerConfig())
    assert m.cfg.energy == FaasSimConfig().energy
    assert m.cfg.cold_start == FaasSimConfig().cold_start
