"""Host spans on the profiler's clock, with an always-on aggregate per name.

``span(name)`` enters ``jax.profiler.TraceAnnotation(name)``, so a
profiled run shows the span on the same clock as the device's operations,
and adds the span's wall time to an in-memory aggregate for its name:
count, total seconds, longest and last. No per-event list is kept, so
memory stays bounded however long the run.

``register_program`` records what a compiled round exposes for readers
(its HLO module name and its instruction -> ``fedfog.*`` scope map) as
plain data: never the executable, whose device memory must go when its
owner drops it.

Names used by the program (``PERF.md`` section 3 maps each to its metric):

- set-up: ``fedfog.setup.lower``, ``fedfog.setup.compile``,
  ``fedfog.setup.contract``, ``fedfog.setup.init_state``;
- each round: ``fedfog.round.inputs``, ``fedfog.round`` (dispatch),
  ``fedfog.round.read``, ``fedfog.round.telemetry``, ``fedfog.checkpoint``.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax


@dataclasses.dataclass
class Aggregate:
    count: int = 0
    total_s: float = 0.0
    longest_s: float = 0.0
    last_s: float = 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.longest_s = max(self.longest_s, seconds)
        self.last_s = seconds


class Span:
    """One open span; ``seconds`` holds its wall time once it has closed."""

    __slots__ = ("name", "seconds", "_rec", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str):
        self.name, self.seconds, self._rec = name, None, rec

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._rec.add(self.name, self.seconds)


class Recorder:
    """Span aggregates and round programs of one process."""

    def __init__(self):
        self._lock = threading.Lock()
        self._aggs: dict[str, Aggregate] = {}
        self._programs: list[dict] = []

    def span(self, name: str) -> Span:
        return Span(self, name)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._aggs.setdefault(name, Aggregate()).add(seconds)

    def stats(self) -> dict[str, Aggregate]:
        """A copy of every name's aggregate."""
        with self._lock:
            return {k: dataclasses.replace(v) for k, v in self._aggs.items()}

    def register_program(self, module: str, phases: dict, heads: dict) -> None:
        with self._lock:
            self._programs.append(
                {"module": module, "phases": dict(phases), "heads": dict(heads)}
            )

    def programs(self) -> list[dict]:
        """Each round program built in this process, oldest first:
        ``module`` (HLO module name), ``phases`` (instruction -> scope) and
        ``heads`` (instruction -> "TYPE opcode(operand,...)", which a
        device trace's event name prints too)."""
        with self._lock:
            return list(self._programs)

    def reset(self) -> None:
        with self._lock:
            self._aggs.clear()
            self._programs.clear()


RECORDER = Recorder()
span = RECORDER.span
stats = RECORDER.stats
register_program = RECORDER.register_program
programs = RECORDER.programs
reset = RECORDER.reset
