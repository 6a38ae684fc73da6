"""Metrics registry: counters/gauges/histograms with pluggable sinks.

Design constraints, in order:

1. **Never add a device sync.** Per-step scalars come out of the jitted
   train step as aux outputs (already the Trainer's contract); the registry
   buffers the *device* arrays via :meth:`MetricsRegistry.record_step` and
   fetches them in ONE ``jax.device_get`` at :meth:`flush_steps` — called
   on the StepTimer's sync cadence or at epoch end, when the host was going
   to block anyway.
2. **One canonical record shape.** Every emission — step, epoch, eval,
   system — is a flat JSON-serializable dict ``{"ts": float, "kind": str,
   **values}``. ``RunLogger.log_metrics`` consumes exactly this shape (via
   :class:`LoggerSink`), ``tools/metrics_report.py`` parses exactly this
   shape, and tests round-trip it.
3. **Sinks are dumb.** A sink implements ``write(record: dict)`` and
   optionally ``close()``. The registry fans each record out to all of
   them; a sink must never raise into the training loop (JSONL write
   failures degrade to a dropped record, not a dead run).

Instruments follow the Prometheus naming because it is the vocabulary
every operator already knows: ``Counter`` (monotonic, ``inc``), ``Gauge``
(set-to-current), ``Histogram`` (observations + percentile summary).
"""

from __future__ import annotations

import json
import math
import time
from pathlib import Path
from typing import Any, Iterable, Mapping

import jax


def _jsonable(v: Any) -> Any:
    """Coerce numpy/JAX scalars to plain floats; leave JSON types alone."""
    if isinstance(v, (str, bool, int, type(None))):
        return v
    if isinstance(v, float):
        return v if math.isfinite(v) else None
    try:
        f = float(v)
    except (TypeError, ValueError):
        return str(v)
    return f if math.isfinite(f) else None


def labeled(name: str, **labels: str) -> str:
    """Prometheus-style labeled instrument name: ``name{k="v",...}``.

    The registry keys instruments by plain string, so labels are an encoding
    convention, not a type: ``labeled("serve_shed_total", reason="deadline")``
    → ``serve_shed_total{reason="deadline"}``. Keys are sorted so the same
    label set always maps to the same instrument, whatever the call-site
    spelling. The base (unlabeled) counter is maintained separately by
    callers — `snapshot()` reports both.
    """
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonically increasing count (steps run, tokens seen, bytes moved)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease ({amount})")
        self.value += amount


class Gauge:
    """Point-in-time value (HBM bytes in use, learning rate, MFU)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.value: float | None = None

    def set(self, value: float) -> None:
        self.value = float(value)


class Histogram:
    """Observation stream with percentile summaries (step latency)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.observations: list[float] = []

    def observe(self, value: float) -> None:
        self.observations.append(float(value))

    def percentile(self, q: float) -> float | None:
        """Nearest-rank percentile over everything observed; ``q`` in [0, 1]."""
        if not self.observations:
            return None
        d = sorted(self.observations)
        return d[int(q * (len(d) - 1))]

    def summary(self) -> dict[str, float]:
        if not self.observations:
            return {}
        return {
            "count": float(len(self.observations)),
            "mean": sum(self.observations) / len(self.observations),
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "max": max(self.observations),
        }


class InMemorySink:
    """Keeps every record in a list — tests and ad-hoc inspection."""

    def __init__(self) -> None:
        self.records: list[dict] = []

    def write(self, record: dict) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass


class JsonlSink:
    """One JSON object per line, flushed per record so a crashed run still
    has its telemetry (the metrics file doubles as a black box)."""

    def __init__(self, path: str | Path) -> None:
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._f = self.path.open("a")

    def write(self, record: dict) -> None:
        self._f.write(json.dumps(record) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


class LoggerSink:
    """Adapter onto ``RunLogger.log_metrics`` — the canonical record IS the
    RunLogger record (satellite: one schema, two consumers)."""

    def __init__(self, logger: Any) -> None:
        self._logger = logger

    def write(self, record: dict) -> None:
        self._logger.log_metrics(record)

    def close(self) -> None:
        pass


class TensorBoardSink:
    """Optional scalar export. Soft dependency: constructing it without a
    TensorBoard writer available raises ImportError — callers gate on it;
    nothing else in the registry imports tensorboard."""

    def __init__(self, log_dir: str | Path) -> None:
        try:
            from flax.metrics import tensorboard as _tb  # type: ignore

            self._writer = _tb.SummaryWriter(str(log_dir))
        except ImportError:
            try:
                from torch.utils import tensorboard as _tb  # type: ignore

                self._writer = _tb.SummaryWriter(str(log_dir))
            except ImportError as e:
                raise ImportError(
                    "TensorBoardSink needs flax.metrics.tensorboard or "
                    "torch.utils.tensorboard"
                ) from e

    def write(self, record: dict) -> None:
        step = int(record.get("step", record.get("epoch", 0)) or 0)
        for key, value in record.items():
            if key in ("ts", "kind", "step", "epoch"):
                continue
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                self._writer.scalar(f"{record.get('kind', 'run')}/{key}", value, step)

    def close(self) -> None:
        self._writer.flush()


class MetricsRegistry:
    """Named instruments + record emission + step-scalar buffering.

    ``emit(kind, values)`` is the only path a record takes to the sinks, so
    the canonical shape is enforced in one place. ``record_step`` /
    ``flush_steps`` implement the no-extra-syncs contract described in the
    module docstring.
    """

    def __init__(self, sinks: Iterable[Any] = ()) -> None:
        self.sinks: list[Any] = list(sinks)
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}
        # [(step, {name: device-or-host scalar})] awaiting one device_get.
        self._pending_steps: list[tuple[int, dict[str, Any]]] = []

    # -- instruments (get-or-create, Prometheus style) ---------------------
    def counter(self, name: str) -> Counter:
        return self._counters.setdefault(name, Counter(name))

    def gauge(self, name: str) -> Gauge:
        return self._gauges.setdefault(name, Gauge(name))

    def histogram(self, name: str) -> Histogram:
        return self._histograms.setdefault(name, Histogram(name))

    # -- sinks -------------------------------------------------------------
    def add_sink(self, sink: Any) -> None:
        self.sinks.append(sink)

    def emit(self, kind: str, values: Mapping[str, Any]) -> dict:
        """Fan one canonical record out to every sink; returns the record."""
        record = {"ts": time.time(), "kind": kind}
        record.update({k: _jsonable(v) for k, v in values.items()})
        for sink in self.sinks:
            try:
                sink.write(record)
            except Exception:
                pass  # a sink must never kill the training loop
        return record

    # -- per-step scalars out of the jitted step ---------------------------
    def record_step(self, step: int, scalars: Mapping[str, Any]) -> None:
        """Buffer one step's aux-output scalars WITHOUT reading them.

        ``scalars`` values may be live device arrays; they are not fetched
        here — the train loop keeps running ahead of the device.
        """
        self._pending_steps.append((step, dict(scalars)))

    def flush_steps(self, extra: Mapping[str, Any] | None = None) -> list[dict]:
        """One ``jax.device_get`` for everything buffered, then emit one
        ``"step"`` record per step. ``extra`` keys (e.g. the step-duration
        estimates the StepTimer attributed to this window) are merged into
        every record of the flush."""
        if not self._pending_steps:
            return []
        pending, self._pending_steps = self._pending_steps, []
        fetched = jax.device_get([s for _, s in pending])
        extra = dict(extra or {})
        out = []
        for (step, _), scalars in zip(pending, fetched):
            values = {"step": step, **scalars, **extra}
            out.append(self.emit("step", values))
        return out

    def drop_pending_steps(self) -> int:
        """Discard the buffered (unfetched) step scalars; returns the count.

        Rollback path (numerics guardrails): a ``poisoned`` verdict means
        the steps since the episode opened never happened — their buffered
        records must not reach the sinks as if they were real training
        progress. Dropping device references is free (no device_get).
        """
        n = len(self._pending_steps)
        self._pending_steps.clear()
        return n

    def snapshot(self) -> dict[str, Any]:
        """Current instrument values as one flat dict (for epoch records)."""
        snap: dict[str, Any] = {}
        for c in self._counters.values():
            snap[c.name] = c.value
        for g in self._gauges.values():
            if g.value is not None:
                snap[g.name] = g.value
        for h in self._histograms.values():
            for stat, v in h.summary().items():
                snap[f"{h.name}_{stat}"] = v
        return snap

    def close(self) -> None:
        try:
            self.flush_steps()  # a crashed/short run still keeps its buffer
        except Exception:
            pass
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:
                pass
