"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json`` (its configuration,
``bench/configs/<config>.json``); the metrics it reports are those of
``BENCHMARK.json`` that name the cell, or name no cells. Set-up runs from
process start to the window's start; the window runs ``--seconds``; then
the program's state is freed and the plain reference checks what the
timed path produced. The last line of standard output is the result;
without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints none.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
for p in (BENCH, os.path.join(BENCH, "refs")):
    if p not in sys.path:
        sys.path.insert(0, p)

import common  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    return ap.parse_args(argv)


def cell_metrics(bench: dict, name: str) -> tuple:
    """(end-to-end, per-layer) metric entries of ``BENCHMARK.json`` that
    this cell reports."""
    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def make_driver(cell: dict, devices):
    """The cell's driver, ``drivers/<driver>.py``, found by name: its
    ``Cell`` sets up the timed path (``start``), runs the window
    (``window``), frees the program's state (``stop``) and has the plain
    reference read what the path produced (``check``)."""
    import importlib

    try:
        mod = importlib.import_module("drivers." + cell["driver"])
    except ModuleNotFoundError:
        raise SystemExit(f"bench: no driver {cell['driver']!r}") from None
    return mod.Cell(cell, devices)


def main(argv=None, *, require_tpu: bool = True, data_root: str = BENCH,
         benchmark: str | None = None) -> int:
    args = parse_args(argv)
    clock_t0 = _T0 if require_tpu else time.perf_counter()
    cell = common.find_cell(args.workload, data_root)
    bench = common.load_json(benchmark or os.path.join(common.CHECKOUT,
                                                       "BENCHMARK.json"))
    e2e, per_layer = cell_metrics(bench, args.workload)
    if require_tpu:
        common.use_cache()
    common.import_program()
    import jax

    chips = cell.get("chips", 1)
    if require_tpu:
        device = common.require_chip(chips)
    else:
        device = common.device_info(jax.devices()[:chips])
    devices = jax.devices()[:chips]
    counter = common.CompileCounter()

    import xplane as trace

    span = trace.span if args.trace else (lambda name: contextlib.nullcontext())
    driver = make_driver(cell, devices)
    prog = driver.start(args.seed)
    setup_s = time.perf_counter() - clock_t0
    before = counter.snapshot()
    print(f"[bench] set-up {setup_s:.3f} s; compile cache {before}",
          file=sys.stderr, flush=True)

    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, cell["traffic"]["trace_seconds"])
        with trace.recording() as rec:
            with span("bench.window"):
                work = driver.window(seconds, span)
    else:
        rec = None
        work = driver.window(seconds, span)
    after = counter.snapshot()
    in_window = after["compiles"] - before["compiles"]
    print(f"[bench] compiles in the window: {in_window}", file=sys.stderr,
          flush=True)
    device["memory_peak_bytes"] = common.memory_peak(devices)
    driver.stop()
    gc.collect()

    t_ref = time.perf_counter()
    r = driver.check(args.seed, prog)
    checks = [common.Check(k, r[k], v) for k, v in cell["limits"].items()]
    print(f"[bench] reference {time.perf_counter() - t_ref:.1f} s; readings "
          f"{json.dumps(r)}", file=sys.stderr, flush=True)
    correct = all(c.ok for c in checks) and work["failed"] == 0

    metrics = {}
    if args.trace:
        t = rec.trace
        lo, hi = t.window()
        device["busy_s"] = trace.busy_s(t)
        device["window_s"] = hi - lo
        kind = device["kind"]
        import counts

        ctx = {"trace": t, "work": work, "cell": cell, "chips": chips,
               "peak": counts.peaks(kind) if require_tpu else
               counts.PEAKS["TPU v5 lite"]}
        readers = common.metric_readers([m["name"] for m in per_layer])
        for m in per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(work, setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result = {"correct": correct, "attempted": work["attempted"],
              "failed": work["failed"], "metrics": metrics, "device": device,
              "compiles_in_window": in_window}
    if args.trace:
        result["breakdown"] = trace.breakdown(rec.trace)
        ops = trace.breakdown(rec.trace, top=40)["device_ops"]
        print(f"[bench] device ops by time: {json.dumps(ops)}", file=sys.stderr,
              flush=True)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except common.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
