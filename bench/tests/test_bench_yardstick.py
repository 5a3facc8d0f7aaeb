"""The benchmark's yardstick on the CPU: trace reduction, operation and
byte counts, traffic generators, finding cells by name, and the runner's
refusal to run without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest_helpers import BENCH, DATA  # noqa: F401  (sets sys.path)

import common
import counts
import traffic
import xplane

REPO = os.path.dirname(BENCH)


def _trace():
    """Device ops on [0, 10] s: busy [1, 3] (two overlapping ops) and
    [5, 6]; spans open around [0.5, 4] (round) and the whole window."""
    ops = [("fusion.1", 1.0, 2.5), ("kernel", 2.0, 3.0), ("kernel", 5.0, 6.0),
           ("fusion.2", 11.0, 12.0)]
    spans = [("bench.window", 0.0, 10.0), ("bench.round", 0.5, 4.0)]
    return xplane.Trace(ops=[ops], spans=spans)


def test_idle_share_from_hand_made_intervals():
    t = _trace()
    assert xplane.busy_s(t) == pytest.approx(3.0)
    assert xplane.union_length([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert xplane.gaps([(1, 3), (5, 6)], 0, 10) == [(0, 1), (3, 5), (6, 10)]


def test_kernel_time_by_name_and_breakdown():
    t = _trace()
    assert xplane.op_seconds(t, lambda n: n == "kernel") == pytest.approx(2.0)
    b = xplane.breakdown(t)
    assert b["device_ops"][0] == ["kernel", 2.0]
    assert b["idle_gaps"][0] == ["outside bench spans", 4.0]
    assert ["bench.round", 2.0] in b["idle_gaps"]


def test_recorded_trace_reduces():
    path = os.path.join(DATA, "trace")
    if not os.path.isdir(path):
        pytest.fail("the recorded chip trace is missing from bench/tests/data")
    t = xplane.read(path)
    lo, hi = t.window()
    busy = xplane.busy_s(t)
    assert t.ops and 0 < busy <= hi - lo
    with open(os.path.join(DATA, "trace", "expected.json")) as f:
        want = json.load(f)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert xplane.op_seconds(t, lambda n: want["op"] in n) == pytest.approx(
        want["op_seconds"], rel=1e-9)


def _tiny_rwkv():
    return {"num_layers": 2, "d_model": 128, "d_ff": 256, "vocab_size": 512}


def test_rwkv6_flops_by_hand():
    import rwkv6

    sz = _tiny_rwkv()
    d, ff = 128, 256
    mix = d * 160 + 160 * d  # maa_w1, maa_w2
    decay = d * 64 + 64 * d
    proj = 5 * d * d + d * d  # r, k, v, g, o; channel-mix receptance
    cm = d * ff + ff * d
    head = d * 512
    w = 2 * (mix + decay + proj + cm) + head
    assert rwkv6.matmul_weights(sz) == w
    assert rwkv6.recurrence_flops(sz) == 2 * 7 * 2 * 64 * 64
    assert rwkv6.train_flops_per_token(sz) == 6 * w + 3 * 2 * 7 * 2 * 4096


def test_round_mfu_takes_the_configurations_count(monkeypatch):
    """round_mfu counts with the cell's own reference, and refuses a
    reference that brings no count."""
    import types

    import rwkv6

    reader = common.metric_readers(["round_mfu"])["round_mfu"]
    t = xplane.Trace(ops=[[]], spans=[("bench.window", 0.0, 2.0)])
    cell = common.find_cell("round.rwkv6-1.6b-l5.local")
    ctx = {"trace": t, "work": {"tokens": 1000}, "cell": cell, "chips": 1,
           "peak": counts.PEAKS["TPU v5 lite"]}
    want = 100 * rwkv6.train_flops_per_token(cell["cfg"]["sizes"]) * 1000 / (
        2.0 * 197e12)
    assert reader.read(ctx) == pytest.approx(want)
    monkeypatch.setattr(common, "reference_module",
                        lambda cell: types.SimpleNamespace())
    with pytest.raises(SystemExit):
        reader.read(ctx)


def test_delta_pipeline_bytes_by_hand():
    assert counts.delta_pipeline_bytes(2, 10, 4) == 4 * 2 * 4 + 2 * 10 + 8 * 4


def test_round_traffic_repeats_for_a_seed():
    cell = common.find_cell("round.rwkv6-tiny.local", DATA)
    tr = cell["traffic"]

    def rounds(seed, n=3):
        feed = traffic.RoundInputs(tr, 512, 2, 64, common.seed_key(seed))
        return [{k: np.asarray(v) for k, v in feed.next().items()}
                for _ in range(n)]

    a, b, c = rounds(3_000_000_001), rounds(3_000_000_001), rounds(5)
    for x, y in zip(a, b):
        for key in x:
            np.testing.assert_array_equal(x[key], y[key])
    assert not np.array_equal(a[2]["tokens"], c[2]["tokens"])
    assert a[0]["tokens"].shape == (2 * tr["local_steps"] * tr["batch_per_slot"],
                                    tr["seq_len"] + 1)


def test_round_telemetry_is_the_launchers():
    """The cell's telemetry steps as ``launch/train.py`` steps it: the
    energy level is the battery, which drains by ``drain_per_round`` on
    the two clients that held a slot and recharges elsewhere."""
    cell = common.find_cell("round.rwkv6-1.6b-l5.local")
    tr = dict(cell["traffic"], seq_len=4, local_steps=1, batch_per_slot=1)
    tel = tr["telemetry"]
    feed = traffic.RoundInputs(tr, 512, 2, 64, common.seed_key(2 ** 33 + 7))
    b0, b1 = (feed.next() for _ in range(2))
    batt0, batt1 = np.asarray(b0["telemetry_batt"]), np.asarray(b1["telemetry_batt"])
    np.testing.assert_array_equal(np.asarray(b0["telemetry_energy"]), batt0)
    assert batt0.min() >= tel["init"][0] and batt0.max() <= tel["init"][1]
    want = np.clip(batt0 + tel["recharge"], 0, 1)
    want[:2] = np.clip(batt0[:2] - tel["drain_per_round"], 0, 1)
    np.testing.assert_allclose(batt1, want, rtol=1e-6)
    for k in ("telemetry_cpu", "telemetry_mem"):
        assert not np.array_equal(np.asarray(b0[k]), np.asarray(b1[k]))
        assert 0.05 <= np.asarray(b1[k]).min() and np.asarray(b1[k]).max() <= 1


def test_a_dropped_in_cell_is_found(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "workloads"), root / "workloads")
    shutil.copytree(os.path.join(BENCH, "configs"), root / "configs")
    src = json.load(open(os.path.join(BENCH, "workloads",
                                      "round.rwkv6-1.6b-l5.local.json")))
    src["traffic"]["local_steps"] = 8
    (root / "workloads" / "round.rwkv6-1.6b-l5.deep.json").write_text(
        json.dumps(src))
    cell = common.find_cell("round.rwkv6-1.6b-l5.deep", str(root))
    assert cell["traffic"]["local_steps"] == 8
    assert cell["cfg"]["sizes"]["d_model"] == 2048
    with pytest.raises(SystemExit):
        common.find_cell("round.rwkv6-1.6b-l5.absent", str(root))


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "round.rwkv6-1.6b-l5.local", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_runner_refuses_without_a_tpu():
    p = _run_bench(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_runner_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run_bench(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_names_a_file_for_everything():
    b = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
    for w in b["workloads"]:
        cell = common.find_cell(w["name"])
        assert (cell["config"], cell["mix"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
