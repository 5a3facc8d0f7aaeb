"""Readings that set a cell's correctness limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control 3 --out FILE

For each seed: the program's sound reading (the timed path from the seed,
compared with the plain reference, as ``run.py`` compares it). For the
first ``--control`` seeds also the control (the reference computed with
float8 products, put in the program's place) and, for round cells, the
fault that leaves half of each batch out (planted in the reference).
One JSON line per reading goes to ``--out`` and to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
for p in (BENCH, os.path.join(BENCH, "refs")):
    if p not in sys.path:
        sys.path.insert(0, p)

import common  # noqa: E402


def _as_program(ref: dict) -> dict:
    return {"losses": ref["losses"], "mu_norms": ref["mu_norms"],
            "admitted": [int(sum(m)) for m in ref["masks"]],
            "eligible": ref["eligible"], "change": ref["change"]}


def calibrate_round(cell, devices, seeds, n_control, emit):
    from drivers.round import RoundCell, readings

    ref_mod = common.reference_module(cell)
    rc = RoundCell(cell, devices)
    for i, seed in enumerate(seeds):
        prog = rc.start(seed)
        rc.state = None
        gc.collect()
        ref = rc.reference(seed, ref_mod)
        prog["change"] = rc.program_change(prog, seed)
        emit({"seed": seed, "kind": "program", **readings(prog, ref, rc.leaf_names),
              "losses_program": prog["losses"], "losses_reference": ref["losses"]})
        if i < n_control:
            for name, kw in (("control_fp8", {"quant": "fp8"}),
                             ("fault_half_batch", {"half": True})):
                low = rc.reference(seed, ref_mod, **kw)
                emit({"seed": seed, "kind": name,
                      **readings(_as_program(low), ref, rc.leaf_names)})
                del low
        del ref, prog
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = common.find_cell(args.workload)
    common.use_cache()
    common.import_program()
    import jax

    chips = cell.get("chips", 1)
    common.require_chip(chips)
    devices = jax.devices()[:chips]
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        t0 = time.perf_counter()

        def emit(rec):
            rec = dict(rec, workload=args.workload,
                       elapsed_s=time.perf_counter() - t0)
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        calibrate_round(cell, devices, seeds, args.control, emit)


if __name__ == "__main__":
    try:
        main()
    except common.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
