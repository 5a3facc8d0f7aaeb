"""The round's phase and set-up readers on the CPU: device phases from the
program's registered map on hand-made traces, with a colliding
instruction name from another program; an empty map; a program that
registers nothing; the set-up spans; and a whole traced tiny run."""
from __future__ import annotations

import json
import os
import sys

import pytest

from conftest_helpers import DATA, SEED  # (sets sys.path)

import common
import counts
import phases
import xplane

CELL = "round.rwkv6-tiny.local"
NEW = ("local_train_mfu", "server_step_ms", "schedule_ms", "setup_compile_s",
       "setup_contract_s")

# The round module as its compiled text prints it: a local-training loop
# whose body fusion runs inside it, the server step and the gate.
HEADS = {"while.2": ("fedfog.local_train", "(s32[], f32[8]{0}) while(p)"),
         "fusion.7": ("fedfog.local_train", "f32[8]{0} fusion(p)"),
         "fusion.1": ("fedfog.server", "f32[1024]{0:T(1024)} fusion(p)"),
         "compare.3": ("fedfog.schedule", "pred[32]{0} compare(p)")}


def _event(name, operand="p"):
    head = HEADS[name][1].rsplit("(", 1)[0]
    return (f"%{name} = {head}(f32[8]{{0:T(8,128)}} %{operand}), kind=kLoop,"
            f" calls=%fused_computation.3")


def _trace():
    """Two rounds on [0, 10] s. Round ops: gate [0.5, 1], loop [1, 4] with
    its body [1.5, 2.5], server [4, 5]; gate [6, 6.5], loop [6.5, 8] with
    a cut-short event name, server [8, 8.5]. The feed's own ``fusion.1``
    runs [5, 5.5] (another type) and [5.5, 6] (the round's type, another
    operand)."""
    ops = [(_event("compare.3"), 0.5, 1.0), (_event("while.2"), 1.0, 4.0),
           (_event("fusion.7"), 1.5, 2.5), (_event("fusion.1"), 4.0, 5.0),
           ("%fusion.1 = u32[2]{0} fusion(u32[2]{0} %key), kind=kLoop",
            5.0, 5.5),
           (_event("fusion.1", operand="fusion.253"), 5.5, 6.0),
           (_event("compare.3"), 6.0, 6.5),
           ("%while.2 = (s32[], f32[", 6.5, 8.0),
           (_event("fusion.1"), 8.0, 8.5)]
    spans = [("bench.window", 0.0, 10.0)]
    return xplane.Trace(ops=[ops], spans=spans)


@pytest.fixture
def program(monkeypatch):
    from repro.obs import spans

    rec = spans.Recorder()
    rec.register_program("jit_round_fn", {n: s for n, (s, _) in HEADS.items()},
                         {n: h for n, (_, h) in HEADS.items()})
    monkeypatch.setattr(spans, "programs", rec.programs)
    return rec


def _ctx(tr, rounds=2, tokens=1000):
    return {"trace": tr, "work": {"rounds": rounds, "tokens": tokens},
            "cell": common.find_cell("round.rwkv6-1.6b-l5.local"), "chips": 1,
            "peak": counts.PEAKS["TPU v5 lite"]}


def _read(name, ctx):
    return common.metric_readers([name])[name].read(ctx)


def test_phases_per_round_with_a_colliding_name(program):
    ctx = _ctx(_trace())
    assert _read("server_step_ms", ctx) == pytest.approx(1e3 * 1.5 / 2)
    assert _read("schedule_ms", ctx) == pytest.approx(1e3 * 1.0 / 2)
    import rwkv6

    flops = rwkv6.train_flops_per_token(ctx["cell"]["cfg"]["sizes"]) * 1000
    # The loop and its body count once: 3 + 1.5 s of local training.
    assert _read("local_train_mfu", ctx) == pytest.approx(
        100 * flops / (4.5 * 197e12))


def test_event_signature_is_the_compiled_head():
    """A trace event prints operands typed, and long lists with index
    comments; its signature is the head ``analyze_hlo`` gives the compiled
    line."""
    from repro.dist import analyze_hlo

    tup = ", ".join(["f32[2]{0}"] * 5) + ", /*index=5*/f32[2]{0}"
    text = f"""HloModule jit_round_fn

ENTRY %main.1 (p.1: f32[2]) -> f32[2] {{
  %p.1 = f32[2]{{0}} parameter(0)
  %t.2 = ({tup}) tuple(%p.1, %p.1, %p.1, %p.1, %p.1, /*index=5*/%p.1)
  %w.3 = ({tup}) while(%t.2), condition=%c, body=%b, metadata={{op_name="jit(round_fn)/fedfog.local_train/while"}}
  %f.4 = f32[2]{{0:T(128)}} fusion(%p.1, %p.1, %w.3, %p.1, %p.1, /*index=5*/%p.1), kind=kLoop, calls=%fc, metadata={{op_name="jit(round_fn)/fedfog.server/add"}}
  ROOT %g.5 = f32[2]{{0}} get-tuple-element(%w.3), index=0
}}
"""
    hlo = analyze_hlo(text)
    assert hlo.module == "jit_round_fn"
    assert hlo.phases == {"w.3": "fedfog.local_train",
                          "f.4": "fedfog.server", "g.5": "fedfog.local_train"}
    typed = ", ".join(["f32[2]{0:T(128)}"] * 5)
    events = {
        "w.3": f"%w.3 = ({tup}) while(({tup}) %t.2), condition=%c, body=%b",
        "f.4": (f"%f.4 = f32[2]{{0:T(128)}} fusion(f32[2]{{0}} %p.1, "
                f"f32[2]{{0}} %p.1, ({tup}) %w.3, f32[2]{{0}} %p.1, "
                f"f32[2]{{0}} %p.1, /*index=5*/f32[2]{{0}} %p.1), "
                f"kind=kLoop, calls=%fc"),
        "g.5": f"%g.5 = f32[2]{{0}} get-tuple-element(({typed}) %w.3)",
    }
    for name, event in events.items():
        assert phases.signature(event) == hlo.heads[name], name
    assert phases.signature(events["f.4"][:60]) is None  # cut short


def test_phase_that_matches_nothing_is_an_error(program):
    tr = _trace()
    tr.ops[0] = [op for op in tr.ops[0] if "compare" not in op[0]]
    with pytest.raises(SystemExit, match="fedfog.schedule"):
        _read("schedule_ms", _ctx(tr))


def test_empty_phase_map_names_the_compile_cache(monkeypatch):
    from repro.obs import spans

    rec = spans.Recorder()
    rec.register_program("jit_round_fn", {}, {})
    monkeypatch.setattr(spans, "programs", rec.programs)
    with pytest.raises(SystemExit, match="compile-cache"):
        _read("server_step_ms", _ctx(_trace()))


def test_a_program_without_spans_leaves_the_metrics_out(monkeypatch):
    import repro.obs

    # As the parent tree's program: ``repro.obs`` has no ``spans``.
    monkeypatch.delattr(repro.obs, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    ctx = _ctx(_trace())
    assert {n: _read(n, ctx) for n in NEW} == dict.fromkeys(NEW)


def test_no_device_operations_leave_the_phases_out(program):
    ctx = _ctx(xplane.Trace(ops=[], spans=[("bench.window", 0.0, 1.0)]))
    assert [_read(n, ctx) for n in NEW[:3]] == [None] * 3


def test_setup_readers_sum_the_program_spans(monkeypatch):
    from repro.obs import spans

    rec = spans.Recorder()
    monkeypatch.setattr(spans, "stats", rec.stats)
    assert _read("setup_compile_s", {}) is None
    rec.add("fedfog.setup.lower", 1.5)
    rec.add("fedfog.setup.compile", 20.0)
    rec.add("fedfog.setup.contract", 0.25)
    rec.add("fedfog.setup.contract", 0.5)
    assert _read("setup_compile_s", {}) == 21.5
    assert _read("setup_contract_s", {}) == 0.75


def test_phase_readers_on_the_recorded_chip_trace(monkeypatch):
    """The recorded chip trace's fusion, mapped by the head its compiled
    text prints, reads the fusion's time in the fixture; the copies that
    the map leaves out are not counted."""
    from repro.obs import spans

    t = xplane.read(os.path.join(DATA, "trace"))
    with open(os.path.join(DATA, "trace", "expected.json")) as f:
        want = json.load(f)
    name = xplane.op_head(want["op"])
    rec = spans.Recorder()
    rec.register_program("jit__lambda", {name: "fedfog.server"},
                         {name: "bf16[1024,1024]{1,0:T(8,128)(2,1)} "
                                "fusion(copy-done)"})
    got = phases.phase_seconds(t, rec.programs()[-1], "fedfog.server")
    assert got == pytest.approx(want["op_seconds"], rel=1e-9)
    assert got < xplane.busy_s(t) == pytest.approx(want["busy_s"], rel=1e-9)


def test_traced_tiny_run_reports_the_setup_spans(tmp_path, capsys):
    """A whole traced run on the CPU: the set-up metrics read the
    program's spans; the device phases have no device trace to read."""
    import conftest_helpers

    bench = json.load(open(conftest_helpers.tiny_benchmark(tmp_path)))
    bench["per_layer"] += [{"name": n, "unit": "u", "moves": "round_s",
                            "workloads": conftest_helpers.ROUND} for n in NEW]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    import run

    run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.5",
              "--trace", "1"], require_tpu=False, data_root=DATA,
             benchmark=str(path))
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = res["metrics"]
    assert {"setup_compile_s", "setup_contract_s"} <= set(m)
    assert m["setup_compile_s"]["value"] > m["setup_contract_s"]["value"] > 0
    assert not set(NEW[:3]) & set(m)
