"""A whole run of a tiny round cell on the CPU, past the look for a chip:
sound, it is correct; with the timed path broken underneath (a step that
returns its state unchanged, half of each batch left out, a loss
altered where it is produced), and with the
control (the reference in float8 put in the program's place), it is
not."""
from __future__ import annotations

import dataclasses
import json
import re

from conftest_helpers import DATA, SEED, run_tiny

import common

CELL = "round.rwkv6-tiny.local"


def test_sound_round_is_correct(tmp_path, capsys):
    err = []
    res = run_tiny(tmp_path, capsys, CELL, err=err)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "round_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"gate_gap", "loss_gap", "update_gap",
                                  "change_gap"}
    assert res["compiles_in_window"] == 0
    # The checked rounds refuse slots: none admitted in round 0, one of
    # the two in rounds 1 and 2, in the program as in the reference.
    line = next(x for x in err if x.startswith("[bench] reference"))
    r = json.loads(line.split("readings ", 1)[1])
    assert r["admitted_program"] == r["admitted_reference"] == [0, 1, 1]
    assert r["gate_gap"] == 0


def test_window_repeats_the_checked_job(tmp_path, capsys):
    """Once a job's rounds are done the window starts the job again from
    its first state and inputs, and its rounds repeat the checked ones."""
    err = []
    res = run_tiny(tmp_path, capsys, CELL, err=err)
    line = next(x for x in err if x.startswith("[bench] window:"))
    rounds, jobs = (int(w) for w in re.findall(r"(\d+) (?:rounds|jobs)", line))
    assert rounds == res["attempted"] and jobs >= 1
    assert jobs == (3 + rounds - 1) // 3  # 3 checked rounds, the first job's
    assert float(line.rsplit(" ", 1)[1]) == 0.0


def test_round_trace_reports_per_layer_metrics(tmp_path, capsys):
    res = run_tiny(tmp_path, capsys, CELL, trace=1)
    assert res["correct"] is True
    assert "round_mfu" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_state_left_unchanged_is_caught(tmp_path, capsys, monkeypatch):
    import repro.fl

    real = repro.fl.make_round_fn

    def broken(*a, **kw):
        fn = real(*a, **kw)

        def round_fn(state, batch):
            new, m = fn(state, batch)
            return dataclasses.replace(new, params=state.params,
                                       server_mu=state.server_mu), m

        return round_fn

    monkeypatch.setattr(repro.fl, "make_round_fn", broken)
    assert run_tiny(tmp_path, capsys, CELL)["correct"] is False


def test_altered_loss_is_caught(tmp_path, capsys, monkeypatch):
    import repro.fl

    real = repro.fl.make_round_fn

    def altered(*a, **kw):
        fn = real(*a, **kw)

        def round_fn(state, batch):
            new, m = fn(state, batch)
            return new, dict(m, loss=m["loss"] * 1.01)

        return round_fn

    monkeypatch.setattr(repro.fl, "make_round_fn", altered)
    assert run_tiny(tmp_path, capsys, CELL)["correct"] is False


def test_half_batch_is_caught(tmp_path, capsys, monkeypatch):
    from repro.models import api

    real = api.Model.loss

    def half(self, params, batch, *a, **kw):
        t = batch["tokens"]
        return real(self, params, dict(batch, tokens=t[: t.shape[0] // 2]),
                    *a, **kw)

    monkeypatch.setattr(api.Model, "loss", half)
    assert run_tiny(tmp_path, capsys, CELL)["correct"] is False


def test_control_fails_a_limit():
    """The float8 reference in the program's place fails one of the
    cell's numbers; the sound program passes them all (tiny sizes)."""
    import jax

    from calibrate import _as_program
    from drivers.round import RoundCell, readings

    common.import_program()
    cell = common.find_cell(CELL, DATA)
    ref_mod = common.reference_module(cell)
    rc = RoundCell(cell, jax.devices()[:1])
    seed = SEED
    prog = rc.start(seed)
    rc.stop()
    ref = rc.reference(seed, ref_mod)
    prog["change"] = rc.program_change(prog, seed)
    sound = readings(prog, ref, rc.leaf_names)
    low = readings(_as_program(rc.reference(seed, ref_mod, quant="fp8")), ref,
                   rc.leaf_names)
    lim = cell["limits"]
    assert all(sound[k] <= v for k, v in lim.items()), sound
    assert any(low[k] > v for k, v in lim.items()), low
    assert low["loss_gap"] > 3 * sound["loss_gap"]


def test_gate_follows_the_program_scheduler():
    """The reference's gate (Eq. 3 with the Eq. 10 energy thresholds and
    the container cache) lets through the same clients as the program's
    scheduler, round after round, on the cell's telemetry at 32 clients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import fedround
    import traffic
    from drivers.round import scheduler_config
    from repro.core.scheduler import account_energy, schedule_round
    from repro.core.types import ClientTelemetry, init_scheduler_state
    from repro.sim.des import RoundCostModel

    common.import_program()
    cell = common.find_cell(CELL, DATA)
    tr = dict(cell["traffic"], clients=32)
    sched, n, bins = tr["scheduler"], tr["clients"], 64
    sc = scheduler_config(sched)
    cost = RoundCostModel.from_scheduler(sc)
    flops, tx = 3.0e12, 1.0e9
    base = sc.energy_model.c_cpu * flops + sc.energy_model.c_tx * tx
    feed = traffic.RoundInputs(tr, 512, 2, bins, common.seed_key(SEED))
    gate = fedround.Gate(n, bins, sched, base)
    state = init_scheduler_state(n, bins, sched["theta_e0"])
    seen, energy_refused = [], 0
    for _ in range(12):
        b = jax.device_get(feed.next())
        tel = ClientTelemetry(cpu=jnp.asarray(b["telemetry_cpu"]),
                              mem=jnp.asarray(b["telemetry_mem"]),
                              batt=jnp.asarray(b["telemetry_batt"]),
                              energy=jnp.asarray(b["telemetry_energy"]))
        dec = schedule_round(state, tel, jnp.asarray(b["hist"]), sc)
        sel = dec.selection
        energy_refused += int(jnp.sum((sel.health > sched["theta_h"])
                                      & (sel.drift < sched["theta_d"])
                                      & ~sel.mask))
        spent = cost.energy_j(dec.selection.mask, state.warm, flops, tx)
        state = account_energy(dec.new_state, spent, sc)
        seen.append(gate.step(b))
        assert seen[-1] == int(dec.selection.num_selected)
        np.testing.assert_allclose(np.asarray(gate.theta),
                                   np.asarray(state.theta_e), rtol=1e-5)
    # The thresholds move, and some clients are refused for their energy.
    assert energy_refused > 0 and len(set(seen)) > 2
