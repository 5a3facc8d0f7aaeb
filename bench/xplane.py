"""From a JAX profiler trace to the intervals the metric readers use.

``Trace`` holds, for each device of the run, the intervals of its device
operations (the ``XLA Ops`` line), and the host spans the harness wrote (``bench.*`` annotations), all on
the profiler's one clock, in seconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list  # per device: list of (name, start_s, end_s)
    spans: list  # host: list of (name, start_s, end_s)

    def window(self) -> tuple:
        w = [s for s in self.spans if s[0] == SPAN_PREFIX + "window"]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0][1], w[0][2]


def op_head(name: str) -> str:
    """The HLO instruction's own name from a TPU trace's event name, which
    carries the whole instruction (``%delta_pipeline_apply.1 = ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` (start, end) clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                if e > lo and s < hi)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def busy_s(tr: Trace) -> float:
    lo, hi = tr.window()
    per_dev = [union_length([(s, e) for _, s, e in ops], lo, hi)
               for ops in tr.ops]
    return float(np.mean(per_dev)) if per_dev else 0.0


def op_seconds(tr: Trace, match) -> float:
    """Summed device time, averaged over devices, of operations whose name
    ``match(name)`` accepts, inside the window."""
    lo, hi = tr.window()
    per_dev = [sum(min(e, hi) - max(s, lo) for n, s, e in ops
                   if match(n) and e > lo and s < hi) for ops in tr.ops]
    return float(np.mean(per_dev)) if per_dev else 0.0


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by the innermost harness span open when each began."""
    lo, hi = tr.window()
    by_name: dict = {}
    for n, s, e in tr.ops[0] if tr.ops else []:
        if e > lo and s < hi:
            by_name[n] = by_name.get(n, 0.0) + min(e, hi) - max(s, lo)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    if tr.ops:
        spans = sorted((s for s in tr.spans if s[0] != SPAN_PREFIX + "window"),
                       key=lambda s: s[1])
        for s, e in sorted(gaps([(a, b) for _, a, b in tr.ops[0]], lo, hi),
                           key=lambda g: g[0] - g[1])[:top]:
            open_ = [sp for sp in spans if sp[1] <= s < sp[2]]
            label = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
                else "outside bench spans"
            idle.append([label, e - s])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": idle}


def read(path: str) -> Trace:
    """Reduce the ``.xplane.pb`` under ``path``."""
    import jax

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise ValueError(f"no profiler trace under {path}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            o = [(ev.name, ev.start_ns * 1e-9,
                  (ev.start_ns + ev.duration_ns) * 1e-9)
                 for line in plane.lines if line.name == OPS_LINE
                 for ev in line.events]
            if o:
                ops.append(o)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return Trace(ops=ops, spans=spans)


@contextlib.contextmanager
def recording():
    """Profile the body into a temporary directory; yields a holder whose
    ``trace`` is set once the body has ended and the trace is read."""
    import jax

    holder = type("Holder", (), {"trace": None})()
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        holder.trace = read(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
