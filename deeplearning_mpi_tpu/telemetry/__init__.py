"""Unified telemetry: metrics registry + sinks, trace annotations, and
derived accounting (analytic FLOPs → MFU, HBM usage, collective bytes).

The split, by question answered:

- :mod:`.registry` — *what happened*: counters/gauges/histograms, per-step
  device scalars buffered without extra syncs, canonical JSONL records.
- :mod:`.trace` — *where time went*: named scopes + trace annotations on
  every parallel hot path, so profiler timelines are readable.
- :mod:`.spans` — *what happened to THIS request/step*: explicit spans with
  a cross-process correlation key, per-process JSONL recorders with clock
  alignment, and the crash flight recorder. ``tools/trace_report.py``
  merges them into a Perfetto timeline.
- :mod:`.flops` — *how fast it could have been*: analytic per-model FLOPs
  and MFU against device peak.
- :mod:`.memory` — *how close to the HBM wall*: ``device.memory_stats()``.
- :mod:`.comms` — *what crossed the wires*: static collective-byte
  accounting from shapes and mesh axis sizes.

``tools/metrics_report.py`` renders the JSONL these produce into the
summary table; ``docs/OBSERVABILITY.md`` explains the columns.
"""

from deeplearning_mpi_tpu.telemetry.registry import (
    InMemorySink,
    JsonlSink,
    LoggerSink,
    MetricsRegistry,
    TensorBoardSink,
    labeled,
)
from deeplearning_mpi_tpu.telemetry.spans import Span, SpanRecorder
from deeplearning_mpi_tpu.telemetry.trace import annotate

__all__ = [
    "InMemorySink",
    "JsonlSink",
    "LoggerSink",
    "MetricsRegistry",
    "Span",
    "SpanRecorder",
    "TensorBoardSink",
    "annotate",
    "labeled",
]
