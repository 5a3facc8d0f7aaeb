"""repro.obs — streaming observability for the compiled engines.

Pluggable trackers (``trackers``), in-scan ``io_callback`` metric taps
(``tap``), the shared history/summary schema (``history``), and host
spans on the profiler's clock with per-name aggregates and the round's
phase map (``spans``). See docs/EXPERIMENTS.md §Observability for the
event/column ↔ §IV.F metric map, the span and scope names, and the CLI
surface (``--track jsonl:PATH``).
"""
from repro.obs.history import (
    assemble_async_history,
    finalize_history,
    summary_metrics,
)
from repro.obs.tap import MetricTap
from repro.obs.trackers import (
    CompositeTracker,
    CsvTracker,
    JsonlTracker,
    MemoryTracker,
    NoopTracker,
    Tracker,
    tracker_from_spec,
)

__all__ = [
    "Tracker",
    "NoopTracker",
    "JsonlTracker",
    "CsvTracker",
    "MemoryTracker",
    "CompositeTracker",
    "tracker_from_spec",
    "MetricTap",
    "finalize_history",
    "summary_metrics",
    "assemble_async_history",
]
