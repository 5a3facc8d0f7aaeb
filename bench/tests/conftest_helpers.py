"""Shared by the benchmark's CPU tests: the tiny cells' data root and a
``BENCHMARK.json`` whose metrics name them."""
from __future__ import annotations

import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
DATA = os.path.join(TESTS, "data")
for p in (BENCH, os.path.join(BENCH, "refs")):
    if p not in sys.path:
        sys.path.insert(0, p)

ROUND = ["round.rwkv6-tiny.local"]
PER_LAYER = [("idle_share.round", ROUND, "round_s"), ("round_mfu", ROUND, "round_s"),
             ("delta_pipeline_roofline", ROUND, "round_s")]


def tiny_benchmark(tmp_path) -> str:
    """A ``BENCHMARK.json`` for the tiny cells: every end-to-end and
    per-layer metric the harness has readers for."""
    b = {"end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "round_s", "unit": "s", "workloads": ROUND}],
        "per_layer": [{"name": n, "unit": "%", "moves": m, "workloads": w}
                      for n, w, m in PER_LAYER]}
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(b, f)
    return path


# At four clients this seed's gate admits no slot in round 0 (drift from
# the uniform start) and one of the two in rounds 1 and 2.
SEED = 3_000_000_013


def run_tiny(tmp_path, capsys, cell: str, seed: int = SEED,
             trace: int = 0, err: list | None = None) -> dict:
    """One whole run of a tiny cell on the CPU, past the harness's look
    for a chip; returns the result line (and appends standard error's
    lines to ``err``)."""
    import run

    run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
              "--trace", str(trace)], require_tpu=False, data_root=DATA,
             benchmark=tiny_benchmark(tmp_path))
    cap = capsys.readouterr()
    if err is not None:
        err.extend(cap.err.splitlines())
    return json.loads(cap.out.strip().splitlines()[-1])
