"""``chip_smoke.py`` on the CPU: every phase at a tiny size, and the
script's refusal to report anything without a TPU or outside a checkout.

The phases are called directly (not ``main()``, which requires a TPU);
their kernels run in interpret mode here, so ``kernel_in_hlo`` is False.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._import_repro()
    return mod


TINY = ("--reduced",)


@pytest.mark.parametrize(
    "phase", ["simulator", "fl_round", "sharded_round", "serving"]
)
def test_phase_runs_tiny(smoke, phase):
    if phase == "simulator":
        res = smoke.phase_simulator(clients=8, topk=4, rounds=2)
        for task in ("har", "emnist"):
            assert res[task]["update_rel_err"] <= smoke.SIM_UPDATE_RTOL
    elif phase == "fl_round":
        res = smoke.phase_fl_round(layers=2, slots=2, rounds=2, extra=TINY)
        assert len(res["losses"]) == 2
        assert res["pipeline"]["mu_rel_err"] <= smoke.PIPELINE_MU_RTOL
    elif phase == "sharded_round":
        # One CPU device: the "sharded" plan is 1 x 1 and must reproduce
        # the one-device round exactly.
        res = smoke.phase_sharded_round(layers=2, slots=2, extra=TINY)
        assert res["update_rel_err"] == 0.0
    else:
        res = smoke.phase_serving(
            slots=3, requests=4, prompt_len=40, gen=6, page=8, extra=TINY
        )
        assert res["completed"] == 4
        check = res["decode_check"]
        assert check["logits_rel_err_max"] <= smoke.LOGITS_RTOL
        assert set(check["attn_err_frac_by_window"]) == {"-1", "32"}


def test_decode_logits_check_ignores_padded_vocab(smoke):
    """hymba's 32001-token vocabulary is padded to 32128 columns masked at
    -1e30; compared with them, any paged-vs-dense difference vanishes."""
    import dataclasses

    import jax

    from repro.configs import get_reduced
    from repro.models import build_model

    cfg = dataclasses.replace(get_reduced("hymba-1.5b"), vocab_size=250)
    assert cfg.padded_vocab > cfg.vocab_size
    model = build_model(cfg)
    res = smoke.check_decode_logits(
        model, model.init(jax.random.PRNGKey(0)), slots=2, prompt_len=24,
        gen=4, page=8,
    )
    assert res["logits_abs_max"] < 1e3
    assert 0.0 < res["logits_rel_err_max"] <= smoke.LOGITS_RTOL


def _reading(stdout: str, phase: str) -> dict:
    """The JSON a phase printed on its ``[phase] {...}`` line."""
    tag = f"[{phase}] "
    lines = [ln for ln in stdout.splitlines() if ln.startswith(tag)]
    assert lines, stdout[-2000:]
    return json.loads(lines[-1][len(tag):])


def _drop_first_client(fn):
    """A planted fault: the kernel aggregates without the first client
    the round admitted."""
    import jax.numpy as jnp

    def faulty(updates, base, mask, *args, **kwargs):
        first = jnp.argmax(mask.astype(jnp.int32))
        return fn(updates, base, mask.at[first].set(False), *args, **kwargs)

    return faulty


def test_simulator_check_catches_a_dropped_client(smoke, monkeypatch,
                                                  capsys):
    """With one admitted client left out of the kernel's aggregate, both
    tasks' kernel-vs-reference update errors exceed ``SIM_UPDATE_RTOL``
    and the phase fails."""
    import repro.kernels.delta_pipeline as dp

    monkeypatch.setattr(dp, "delta_pipeline_apply",
                        _drop_first_client(dp.delta_pipeline_apply))
    with pytest.raises(AssertionError, match="update rel err"):
        smoke.phase_simulator(clients=8, topk=4, rounds=2)
    res = _reading(capsys.readouterr().out, "simulator")
    for task in ("har", "emnist"):
        assert res[task]["update_rel_err"] > smoke.SIM_UPDATE_RTOL


FOUR_DEVICE_ROUND = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke._import_repro()
print("[rtol] {%s}" % ", ".join(
    '"%s": %r' % (k, getattr(smoke, k))
    for k in ("SHARDED_UPDATE_RTOL", "SHARDED_LOSS_RTOL")))
if sys.argv[1] == "drop":
    # A planted fault on the sharded path only: the round on four
    # devices aggregates without its first admitted client.
    import jax.numpy as jnp
    import repro.fl.round as rnd
    sharded = rnd.delta_pipeline_apply_sharded
    def faulty(updates, base, mask, *a, mesh, **k):
        if mesh.size > 1:
            mask = mask.at[jnp.argmax(mask.astype(jnp.int32))].set(False)
        return sharded(updates, base, mask, *a, mesh=mesh, **k)
    rnd.delta_pipeline_apply_sharded = faulty
smoke.phase_sharded_round(layers=2, slots=2, extra=("--reduced",))
"""


@pytest.mark.parametrize("fault", [None, "drop"])
def test_sharded_round_on_four_virtual_devices(fault):
    """The ``--chips 4`` phase on four fake CPU devices (the flag must
    precede JAX's start-up, hence the child): the 2 x 2 plan, its one
    inter-client all-reduce asserted by the launcher, and the update
    within tolerance of the one-device round; with a client dropped from
    the sharded aggregate, beyond it, and the phase fails."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_ROUND, str(fault)], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert "[train] verified: ONE inter-client all-reduce" in proc.stdout
    rtol = _reading(proc.stdout, "rtol")
    res = _reading(proc.stdout, "sharded_round")
    assert res["devices"] == 4
    assert res["loss_rel_err"] <= rtol["SHARDED_LOSS_RTOL"]
    if fault is None:
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert 0.0 < res["update_rel_err"] <= rtol["SHARDED_UPDATE_RTOL"]
    else:
        assert proc.returncode != 0
        assert "update rel err" in proc.stderr
        assert res["update_rel_err"] > rtol["SHARDED_UPDATE_RTOL"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
