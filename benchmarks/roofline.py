"""Roofline analysis (launch brief §Roofline): derive the three terms per
(arch × shape) cell from the dry-run's compiled artifact.

    compute    = HLO_FLOPs_per_device / peak_FLOPs            [s]
    memory     = HLO_bytes_per_device / HBM_bw                [s]
    collective = collective_bytes_per_device / ICI link bw    [s]

Sources: loop-scaled static HLO analysis (dist/hlo_analysis — XLA's own
cost_analysis under-counts while bodies; see module doc) from
results/dryrun/*.json. MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D
(serving) gives the useful-compute ratio.

Emits one row per cell + writes results/roofline.csv for EXPERIMENTS.md.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import Row, fmt
from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, peaks_for
from repro.models import build_model

# CWD-relative, matching repro.launch.dryrun's RESULT_DIR (both halves of
# the pipeline are run from the repo root); fall back to the repo-root
# location when invoked from elsewhere.
DRYRUN_DIR = os.path.join("results", "dryrun")
if not os.path.isdir(DRYRUN_DIR):
    DRYRUN_DIR = os.path.join(
        os.path.dirname(__file__), "..", "results", "dryrun"
    )
OUT_CSV = os.path.join(os.path.dirname(DRYRUN_DIR), "roofline.csv")

# bf16 HLO byte traffic is inflated ~2x by the CPU backend's f32
# legalization of bf16 arithmetic; we report raw parsed bytes (upper bound)
# — noted in EXPERIMENTS.md.


def model_flops_total(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return model.flops_per_token(train=True) * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return model.flops_per_token(train=False) * tokens
    # decode: one token per sequence
    return model.flops_per_token(train=False) * shape.global_batch


def analyze_cell(rec: dict) -> dict | None:
    if rec.get("status") != "OK":
        return None
    chips = 512 if rec["mesh"].startswith("multipod") else 256
    peak = peaks_for(PRODUCTION_DEVICE_KIND)
    flops_dev = rec.get("dot_flops", 0.0)
    bytes_dev = rec.get("hbm_bytes", 0.0)
    coll_dev = rec.get("collective_total", 0.0)
    t_compute = flops_dev / peak.flops_bf16
    t_memory = bytes_dev / peak.hbm_bw
    t_coll = coll_dev / peak.ici_link_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_total(rec["arch"], rec["shape"])
    useful = mf / max(flops_dev * chips, 1e-9)
    bound = max(terms.values())
    frac = (mf / chips / peak.flops_bf16) / max(bound, 1e-12)
    return dict(
        arch=rec["arch"],
        shape=rec["shape"],
        mesh=rec["mesh"],
        t_compute=t_compute,
        t_memory=t_memory,
        t_collective=t_coll,
        dominant=dominant,
        model_flops=mf,
        useful_ratio=useful,
        roofline_fraction=min(frac, 1.0),
        temp_gb=rec.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9,
    )


def load_cells(mesh: str = "single") -> list[dict]:
    out = []
    if not os.path.isdir(DRYRUN_DIR):
        return out
    for arch in ARCH_IDS:
        for shape in SHAPES:
            path = os.path.join(DRYRUN_DIR, f"{arch}__{shape}__{mesh}.json")
            if os.path.exists(path):
                with open(path) as f:
                    out.append(json.load(f))
    return out


def run() -> list[Row]:
    rows = []
    cells = load_cells("single")
    analyzed = []
    n_ok = n_skip = n_fail = 0
    for rec in cells:
        if rec["status"] == "SKIP":
            n_skip += 1
            rows.append(
                Row(
                    f"roofline/{rec['arch']}/{rec['shape']}",
                    0.0,
                    fmt(status="SKIP", reason=rec.get("skip_reason", "")[:40]),
                )
            )
            continue
        if rec["status"] != "OK":
            n_fail += 1
            rows.append(
                Row(
                    f"roofline/{rec['arch']}/{rec['shape']}",
                    0.0,
                    fmt(status="FAIL"),
                )
            )
            continue
        n_ok += 1
        a = analyze_cell(rec)
        analyzed.append(a)
        rows.append(
            Row(
                f"roofline/{rec['arch']}/{rec['shape']}",
                0.0,
                fmt(
                    compute_s=a["t_compute"],
                    memory_s=a["t_memory"],
                    collective_s=a["t_collective"],
                    dominant=a["dominant"],
                    useful_ratio=a["useful_ratio"],
                    roofline_frac=a["roofline_fraction"],
                ),
            )
        )
    if analyzed:
        os.makedirs(os.path.dirname(OUT_CSV), exist_ok=True)
        with open(OUT_CSV, "w") as f:
            cols = list(analyzed[0])
            f.write(",".join(cols) + "\n")
            for a in analyzed:
                f.write(",".join(str(a[c]) for c in cols) + "\n")
    rows.append(
        Row("roofline/summary", 0.0, fmt(ok=n_ok, skip=n_skip, fail=n_fail))
    )
    return rows
