"""Benchmark harness — one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV per the repo contract. Scale with
REPRO_BENCH_SCALE=quick|default|full. Select suites with
``python -m benchmarks.run [suite ...]``. ``--json out.json`` additionally
records the rows (plus scale/timings) as JSON — used by scripts/ci.sh to
keep a ``BENCH_simulator.json`` perf baseline across PRs.

``--compare baseline.json`` prints per-row ``us_per_call`` deltas vs a
previously recorded baseline and exits non-zero when any row regresses
more than the tolerance (default 25%, override with
``--compare-tolerance PCT``). Baselines are machine-specific: compare
against numbers recorded on the same class of machine, and re-record
with ``--json`` when the workload definition changes.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

SUITES = [
    "threshold_sensitivity",  # Table II
    "drift_recovery",  # Table IV
    "robustness",  # Table V
    "ablation",  # Table VI
    "framework_comparison",  # Fig 5/6
    "scalability",  # Fig 8/9
    "orchestration",  # Table IX / Fig 12
    "pareto",  # Fig 2
    "privacy_tradeoff",  # Fig 3
    "hyperparam_sensitivity",  # Fig 10
    "sim_vs_real",  # Tables VII/VIII
    "async_vs_sync",  # event-driven engine: async rules vs round barrier
    "robustness_faults",  # fault & recovery: crash grid, deadline, failover
    "simulator_engine",  # scanned/sweep/async vs looped engine throughput
    "serving",  # continuous batching vs sequential per-request oracle
    "dryrun_sharding",  # dist layer: compile time + collective census
    "kernels_bench",
    "roofline",  # §Roofline (reads results/dryrun)
]


def compare_to_baseline(records, baseline_path, tolerance_pct=25.0) -> int:
    """Print per-row deltas vs a recorded baseline; return the number of
    rows that regressed (slowed down) by more than ``tolerance_pct``.

    Rows are matched by ``name``. The compare is tolerant of shape drift
    in the row set — only SHARED rows can regress:

      * current rows with no baseline entry print ``NEW``;
      * baseline rows the current run did not produce (a renamed or
        removed row in a suite that DID run) warn and are skipped;
      * baseline rows belonging to suites that were not part of this run
        at all (a subset invocation) are ignored silently;
      * zero-baseline rows (summary rows) are skipped — their data lives
        in ``derived``.
    """
    with open(baseline_path) as f:
        base_rows = {
            r["name"]: r for r in json.load(f).get("rows", [])
            if "us_per_call" in r
        }
    run_suites = {rec.get("suite") for rec in records}
    regressions = 0
    print(f"# compare vs {baseline_path} (tolerance {tolerance_pct:.0f}%)")
    for rec in records:
        name = rec.get("name")
        if "us_per_call" not in rec:
            continue
        base = base_rows.pop(name, None)
        if base is None:
            # A row the baseline file predates (e.g. a freshly added
            # benchmark): informational, NOT a regression. It gains a
            # baseline the next time the file is re-recorded with
            # REPRO_BENCH_RECORD=1.
            print(
                f"{name}: NEW (no baseline row — not a regression; "
                f"re-record with REPRO_BENCH_RECORD=1 to baseline it)"
            )
            continue
        old, new = base["us_per_call"], rec["us_per_call"]
        if old <= 0.0:
            continue  # summary rows carry their data in `derived`
        delta = (new - old) / old * 100.0
        flag = ""
        if delta > tolerance_pct:
            flag = "  << REGRESSION"
            regressions += 1
        print(f"{name}: {old:.0f} -> {new:.0f} us/call ({delta:+.1f}%){flag}")
    for name, base in base_rows.items():
        if base.get("suite") not in run_suites:
            continue  # suite not part of this invocation: not comparable
        print(
            f"{name}: skipped (baseline row not produced by this run — "
            f"renamed or removed? re-record with REPRO_BENCH_RECORD=1)"
        )
    return regressions


def main() -> None:
    import importlib

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()

    argv = list(sys.argv[1:])

    def take_flag(flag):
        if flag not in argv:
            return None
        i = argv.index(flag)
        try:
            value = argv[i + 1]
        except IndexError:
            sys.exit(f"{flag} requires an argument")
        del argv[i : i + 2]
        return value

    json_out = take_flag("--json")
    compare_path = take_flag("--compare")
    tolerance = float(take_flag("--compare-tolerance") or 25.0)

    wanted = argv or SUITES
    print("name,us_per_call,derived")
    failures = 0
    records = []
    for suite in wanted:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{suite}")
            for row in mod.run():
                print(row.csv(), flush=True)
                records.append(
                    {
                        "suite": suite,
                        "name": row.name,
                        "us_per_call": row.us_per_call,
                        "derived": row.derived,
                    }
                )
        except Exception as e:  # keep the harness going
            failures += 1
            print(f"{suite}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            records.append({"suite": suite, "name": f"{suite}/ERROR",
                            "error": f"{type(e).__name__}:{e}"})
        print(
            f"# {suite} done in {time.time() - t0:.1f}s", file=sys.stderr, flush=True
        )
        if records and "wall_s" not in records[-1]:
            records[-1]["wall_s"] = round(time.time() - t0, 2)
    regressions = 0
    if compare_path:
        # Compare BEFORE --json possibly rewrites the same baseline file.
        regressions = compare_to_baseline(records, compare_path, tolerance)
    if json_out and regressions:
        # Never replace a baseline with the run that just failed against
        # it — that would reset the perf ratchet to the regressed numbers.
        print(
            f"# NOT writing {json_out}: run regressed vs {compare_path}",
            file=sys.stderr,
        )
    payload = {
        "scale": os.environ.get("REPRO_BENCH_SCALE", "default"),
        "suites": wanted,
        "failures": failures,
        "rows": records,
    }
    if json_out and not regressions:
        with open(json_out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {json_out}", file=sys.stderr)
    if json_out or os.environ.get("REPRO_BENCH_HISTORY"):
        # Longitudinal record: the snapshot baseline above gets
        # overwritten on every re-record; the history file keeps every
        # run (including gate-only --compare runs, when
        # REPRO_BENCH_HISTORY points somewhere) so `python -m
        # benchmarks.history --table` shows the per-row trajectory
        # across PRs.
        from benchmarks.history import append_record

        hist = append_record(payload)
        print(f"# appended history entry to {hist}", file=sys.stderr)
    if regressions:
        print(
            f"# {regressions} row(s) regressed > {tolerance:.0f}%",
            file=sys.stderr,
        )
    if failures or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
