"""Observability subsystem: trackers, in-scan metric taps, shared history.

The standing contracts under test:

  * tap OFF (``tap=None`` or ``every=0``) leaves every engine's history
    bitwise identical to the pre-observability path — the tap is a
    structural gate, not a runtime branch;
  * tap ON streams decimated rows out of the compiled programs mid-run,
    and each streamed row agrees exactly with the final history at its
    sampled step;
  * the tap does not break compile-once: a second tapped ``run_scanned``
    on the same instance is a jit cache hit (one cached executable);
  * engine-health conditions (``lost_inflight``) surface as explicit
    warnings — tracker event when a tracker is attached, plain
    ``warnings.warn`` otherwise;
  * both engines share one finalize schema (``repro.obs.history``).
"""
import json
import warnings

import numpy as np
import pytest

from repro.fl.simulator import FedFogSimulator, SimulatorConfig
from repro.obs import (
    CompositeTracker,
    CsvTracker,
    JsonlTracker,
    MemoryTracker,
    MetricTap,
    NoopTracker,
    finalize_history,
    summary_metrics,
    tracker_from_spec,
)
from repro.sim.events import AsyncConfig, AsyncFedFogSimulator, ChurnConfig


def _cfg(**kw):
    kw.setdefault("task", "emnist")
    kw.setdefault("num_clients", 8)
    kw.setdefault("rounds", 12)
    kw.setdefault("seed", 0)
    return SimulatorConfig(**kw)


# --------------------------------------------------------------------- #
# trackers
# --------------------------------------------------------------------- #
def test_jsonl_tracker_round_trip(tmp_path):
    path = tmp_path / "t.jsonl"
    with JsonlTracker(str(path)) as t:
        t.log({"event": "round", "accuracy": 0.5}, step=3)
        t.log_summary({"final_accuracy": 0.9})
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[0]["step"] == 3 and lines[0]["accuracy"] == 0.5
    assert lines[1]["summary"] is True
    assert lines[1]["final_accuracy"] == 0.9
    assert all("ts" in x for x in lines)


def test_jsonl_rows_visible_mid_run(tmp_path):
    # streaming means rows are flushed as logged, not at close
    path = tmp_path / "t.jsonl"
    t = JsonlTracker(str(path))
    t.log({"event": "round", "x": 1.0}, step=0)
    assert len(path.read_text().splitlines()) == 1
    t.finish()


def test_csv_tracker_round_trip(tmp_path):
    path = tmp_path / "t.csv"
    with CsvTracker(str(path)) as t:
        t.log({"accuracy": 0.5, "energy_j": 1.0}, step=0)
        t.log({"accuracy": 0.6, "energy_j": 2.0, "extra": 9.0}, step=1)
        t.log_summary({"accuracy": 0.6})
    lines = path.read_text().splitlines()
    assert lines[0].split(",")[:2] == ["step", "summary"]
    assert len(lines) == 4  # header + 2 rows + summary
    assert "9.0" not in lines[2]  # unseen key dropped, header is fixed


def test_composite_and_memory_trackers():
    a, b = MemoryTracker(), MemoryTracker()
    with CompositeTracker([a, b]) as t:
        t.log({"x": 1}, step=0)
        t.log_summary({"y": 2})
    assert a.rows == b.rows and len(a.rows) == 1
    assert a.summaries == [{"y": 2}]


def test_tracker_from_spec(tmp_path):
    assert isinstance(tracker_from_spec(None), NoopTracker)
    assert isinstance(tracker_from_spec(""), NoopTracker)
    assert isinstance(tracker_from_spec("noop"), NoopTracker)
    assert isinstance(
        tracker_from_spec(f"jsonl:{tmp_path}/a.jsonl"), JsonlTracker
    )
    assert isinstance(tracker_from_spec(f"csv:{tmp_path}/a.csv"), CsvTracker)
    both = tracker_from_spec(
        f"jsonl:{tmp_path}/b.jsonl,csv:{tmp_path}/b.csv"
    )
    assert isinstance(both, CompositeTracker)
    with pytest.raises(ValueError):
        tracker_from_spec("wandb:project")


# --------------------------------------------------------------------- #
# scan-engine tap
# --------------------------------------------------------------------- #
def test_tap_off_is_bitwise_identical():
    h0 = FedFogSimulator(_cfg()).run_scanned()
    h_none = FedFogSimulator(_cfg(), tap=None).run_scanned()
    # every=0 disables structurally — same trace as tap=None
    h_zero = FedFogSimulator(
        _cfg(), tap=MetricTap(MemoryTracker(), every=0)
    ).run_scanned()
    for k, v in h0.items():
        if isinstance(v, list):
            assert v == h_none[k] == h_zero[k], k


def test_tap_on_does_not_change_history():
    h0 = FedFogSimulator(_cfg()).run_scanned()
    h1 = FedFogSimulator(
        _cfg(), tap=MetricTap(MemoryTracker(), every=3)
    ).run_scanned()
    for k, v in h0.items():
        if isinstance(v, list):
            assert v == h1[k], k


def test_tap_streams_decimated_rows_matching_history():
    mt = MemoryTracker()
    tap = MetricTap(mt, every=4, const={"policy": "fedfog"})
    sim = FedFogSimulator(_cfg(), tap=tap)
    h = sim.run_scanned()
    rows = [r for r in mt.rows if r["event"] == "round"]
    assert [r["step"] for r in rows] == [0, 4, 8]
    assert tap.rows_emitted == len(rows)
    for r in rows:
        assert r["policy"] == "fedfog"
        np.testing.assert_allclose(
            r["accuracy"], h["accuracy"][r["step"]], rtol=1e-6
        )
        np.testing.assert_allclose(
            r["energy_j"], h["energy_j"][r["step"]], rtol=1e-6
        )
    # summary row carries the shared finalize schema
    (s,) = mt.summaries
    assert s["final_accuracy"] == h["final_accuracy"]
    assert s["total_energy_j"] == pytest.approx(h["total_energy_j"])


def test_tapped_scan_compiles_once():
    sim = FedFogSimulator(
        _cfg(), tap=MetricTap(MemoryTracker(), every=5)
    )
    sim.run_scanned()
    sim.run_scanned()
    assert sim._scan_jit._cache_size() == 1


def test_tap_on_loop_engine_matches_scanned_rows():
    mt_scan, mt_loop = MemoryTracker(), MemoryTracker()
    FedFogSimulator(
        _cfg(), tap=MetricTap(mt_scan, every=4)
    ).run_scanned()
    FedFogSimulator(_cfg(), tap=MetricTap(mt_loop, every=4)).run()
    assert [r["step"] for r in mt_scan.rows] == [
        r["step"] for r in mt_loop.rows
    ]
    for rs, rl in zip(mt_scan.rows, mt_loop.rows):
        np.testing.assert_allclose(rs["accuracy"], rl["accuracy"], rtol=1e-6)


def test_aot_rejects_tap():
    sim = FedFogSimulator(_cfg(), tap=MetricTap(MemoryTracker(), every=2))
    with pytest.raises(ValueError, match="tap"):
        sim.aot_scanned()


# --------------------------------------------------------------------- #
# async-engine tap + warnings
# --------------------------------------------------------------------- #
def test_async_tap_off_identical_and_shared_schema():
    h0 = AsyncFedFogSimulator(_cfg(rounds=6), AsyncConfig()).run()
    mt = MemoryTracker()
    h1 = AsyncFedFogSimulator(
        _cfg(rounds=6), AsyncConfig(),
        tap=MetricTap(mt, every=2, channel="flush"),
    ).run()
    for k, v in h0.items():
        if isinstance(v, list):
            assert v == h1[k], k
    # shared finalize schema: async histories now carry cold-start totals
    assert "total_cold_starts" in h1
    rows = [r for r in mt.rows if r["event"] == "flush"]
    assert rows, "tap should stream flush rows"
    for r in rows:
        np.testing.assert_allclose(
            r["accuracy"], h1["accuracy"][r["step"]], rtol=1e-6
        )
    (s,) = mt.summaries
    assert s["num_flushes"] == h1["num_flushes"]


def test_async_vmapped_sweep_path_rejects_tap():
    eng = AsyncFedFogSimulator(
        _cfg(rounds=4), AsyncConfig(),
        tap=MetricTap(MemoryTracker(), every=2),
    )
    with pytest.raises(RuntimeError, match="sweep"):
        eng.metrics_for_seed(0)


def _churny():
    return AsyncConfig.fedbuff(
        4, dispatch_interval_ms=300.0, straggler_sigma=0.4,
        churn=ChurnConfig(arrival_rate=0.2, departure_rate=0.8),
    )


def test_lost_inflight_warns_without_tracker():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        h = AsyncFedFogSimulator(
            _cfg(rounds=10, num_clients=16, top_k=12), _churny()
        ).run()
    assert h["lost_inflight"] > 0
    msgs = [
        str(x.message) for x in w if issubclass(x.category, RuntimeWarning)
    ]
    assert any("in-flight" in m for m in msgs)


def test_lost_inflight_goes_to_tracker_when_attached():
    mt = MemoryTracker()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        h = AsyncFedFogSimulator(
            _cfg(rounds=10, num_clients=16, top_k=12), _churny(),
            tap=MetricTap(mt, every=5, channel="flush"),
        ).run()
    assert h["lost_inflight"] > 0
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]
    warns = [r for r in mt.rows if r["event"] == "warning"]
    assert warns and warns[0]["kind"] == "lost_inflight"
    assert warns[0]["lost_inflight"] == h["lost_inflight"]


# --------------------------------------------------------------------- #
# shared history helpers
# --------------------------------------------------------------------- #
def test_finalize_history_schema():
    h = {"accuracy": [0.1, 0.8, 0.6], "energy_j": [1.0, 2.0, 3.0],
         "round_latency_ms": [10.0, 20.0, 30.0], "cold_starts": [2, 0, 1]}
    finalize_history(h)
    assert h["final_accuracy"] == 0.6
    assert h["peak_accuracy"] == 0.8
    assert h["total_energy_j"] == 6.0
    assert h["mean_latency_ms"] == 20.0
    assert h["total_cold_starts"] == 3
    # empty run degrades to zeros, no crash
    empty = finalize_history({"accuracy": [], "energy_j": []})
    assert empty["final_accuracy"] == 0.0 and empty["total_energy_j"] == 0


def test_summary_metrics_subset():
    h = finalize_history(
        {"accuracy": [0.5], "energy_j": [1.0], "irrelevant": [1, 2]}
    )
    s = summary_metrics(h)
    assert "irrelevant" not in s
    assert s["final_accuracy"] == 0.5


def test_engines_share_finalize_schema():
    h_sync = FedFogSimulator(_cfg(rounds=4)).run_scanned()
    h_async = AsyncFedFogSimulator(_cfg(rounds=4), AsyncConfig()).run()
    for k in ("final_accuracy", "peak_accuracy", "total_energy_j",
              "total_cold_starts"):
        assert k in h_sync and k in h_async, k


# --------------------------------------------------------------------- #
# sweep tracker events
# --------------------------------------------------------------------- #
def test_sweep_tracker_events_and_cache_hits():
    from repro.sim import clear_compile_cache, run_sweep

    clear_compile_cache()
    cfg = _cfg(rounds=4)
    mt = MemoryTracker()
    run_sweep(cfg, seeds=range(2), axes={"lr": [0.01, 0.05]}, tracker=mt)
    groups = [r for r in mt.rows if r["event"] == "sweep_group"]
    assert len(groups) == 1  # one structural signature
    assert groups[0]["n_members"] == 2
    assert groups[0]["cache_hit"] is False
    (s,) = mt.summaries
    assert s["n_points"] == 2 and s["n_compiles"] == 1

    mt2 = MemoryTracker()
    run_sweep(cfg, seeds=range(2), axes={"lr": [0.01, 0.05]}, tracker=mt2)
    assert [r["cache_hit"] for r in mt2.rows
            if r["event"] == "sweep_group"] == [True]


# --------------------------------------------------------------------- #
# host spans (repro.obs.spans)
# --------------------------------------------------------------------- #
def test_span_aggregates_count_total_longest_and_nest():
    import time

    from repro.obs.spans import Recorder

    rec = Recorder()
    with rec.span("outer") as outer:
        for pause in (0.01, 0.03):
            with rec.span("inner") as inner:
                time.sleep(pause)
            assert inner.seconds >= pause
    st = rec.stats()
    assert st["inner"].count == 2 and st["outer"].count == 1
    assert st["inner"].longest_s == st["inner"].last_s >= 0.03
    assert st["inner"].total_s >= 0.04
    assert st["outer"].total_s == outer.seconds >= st["inner"].total_s
    st["inner"].count = 99  # a copy: the recorder's aggregate is untouched
    assert rec.stats()["inner"].count == 2
    rec.register_program("jit_f", {"a": "fedfog.server"}, {"a": "f32[] add"})
    assert rec.programs() == [{"module": "jit_f",
                               "phases": {"a": "fedfog.server"},
                               "heads": {"a": "f32[] add"}}]
    rec.reset()
    assert rec.stats() == {} and rec.programs() == []


def test_span_closes_and_counts_when_its_body_raises():
    from repro.obs.spans import Recorder

    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("fails"):
            raise ValueError("boom")
    assert rec.stats()["fails"].count == 1
