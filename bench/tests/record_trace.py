"""Record the small chip trace that ``test_bench_yardstick`` reduces.

    python3 bench/tests/record_trace.py --out bench/tests/data/trace

Run on a TPU: a few jitted matrix products inside a ``bench.window``
span, profiled; the ``.xplane.pb`` and what the reduction reads from it
(``expected.json``) are written under ``--out``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    out = ap.parse_args().out
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with xplane.span("bench.window"):
        for _ in range(4):
            with xplane.span("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = xplane.read(d)
    top = xplane.breakdown(t)["device_ops"][0][0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)[0], os.path.join(out, "tiny.xplane.pb"))
    with open(os.path.join(out, "expected.json"), "w") as fh:
        json.dump({"busy_s": xplane.busy_s(t), "op": top,
                   "op_seconds": xplane.op_seconds(t, lambda n: top in n),
                   "window": t.window()}, fh, indent=1)
    print(json.dumps(xplane.breakdown(t)))


if __name__ == "__main__":
    main()
