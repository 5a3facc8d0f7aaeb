"""Dryrun/sharding timing: compile time + collective counts per arch.

Spawns ``repro.dist.selftest`` subprocesses (the fake-device flag must be
set before jax initializes, so cells can't run in-process) that build the
mesh plan, jit one FedFog round with the full ShardingRules wiring on an
8-device host mesh, and report compile seconds plus the per-kind
collective census from ``analyze_hlo``. Tracks the perf trajectory of
the distribution layer itself: a regression in rule coverage shows up as
extra collectives; a compile-time regression shows up directly.

Scale: quick = 2 archs, default = 4, full = all 10.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import Row, SCALE, fmt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCHES = {
    "quick": ["llama3.2-1b", "rwkv6-1.6b"],
    "default": ["llama3.2-1b", "mixtral-8x7b", "hymba-1.5b", "rwkv6-1.6b"],
    "full": [
        "qwen2.5-14b", "yi-9b", "gemma3-12b", "llama3.2-1b",
        "moonshot-v1-16b-a3b", "mixtral-8x7b", "seamless-m4t-medium",
        "hymba-1.5b", "rwkv6-1.6b", "internvl2-2b",
    ],
}


def _cell(arch: str) -> dict:
    # Fake CPU devices: the child is pinned to the CPU.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (
        os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m", "repro.dist.selftest", "--json", "--no-check",
         "--arch", arch, "--devices", "8"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{arch}: selftest rc={proc.returncode}: {proc.stderr[-500:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run() -> list[Row]:
    rows = []
    for arch in ARCHES[SCALE]:
        res = _cell(arch)
        counts = res["collective_counts"]
        rows.append(
            Row(
                name=f"dryrun_sharding/{arch}",
                us_per_call=res["compile_s"] * 1e6,
                derived=fmt(
                    platform="cpu",
                    inter_client_ar=res["inter_client_all_reduces"],
                    all_reduce=counts.get("all-reduce", 0),
                    all_gather=counts.get("all-gather", 0),
                    all_to_all=counts.get("all-to-all", 0),
                    permute=counts.get("collective-permute", 0),
                    collective_mb=sum(res["collective_bytes"].values()) / 1e6,
                    ok=res["ok"],
                ),
            )
        )
    return rows
