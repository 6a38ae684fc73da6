"""Profiling and step-level timing — the observability the reference lacks.

The reference's only instrumentation is per-epoch wall-clock written to a log
file (``pytorch/unet/train.py:166,206-211``); there is no profiler, no step
timer, and the DDP all-reduce latency on its hot path
(``pytorch/resnet/main.py:131``) is never measured (``SURVEY.md`` §5.1, §6).
This module supplies both halves TPU-natively:

- :class:`Profiler` wraps ``jax.profiler`` — on-demand XLA/TPU traces
  (HLO timelines, per-op HBM/MXU utilization) viewable in TensorBoard or
  Perfetto, plus a live ``start_server`` port for ``tensorboard --logdir``
  capture on a running job.
- :class:`StepTimer` measures per-step wall time **correctly under JAX's
  async dispatch** (a naive ``time.time()`` around ``train_step`` measures
  Python dispatch, not device compute — the device runs ahead), by
  :func:`host_sync` on a sampling cadence. From it come
  images/sec/chip and step-latency percentiles — the BASELINE.md primary
  metrics.
- :func:`measure_collective_latency` times an N-byte gradient-style
  all-reduce over the mesh's ``data`` axis — the "DDP all-reduce step
  latency" number the baseline asks for, measured the same way on CPU
  meshes and real ICI.
"""

from __future__ import annotations

import statistics
import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp


def host_sync(x: Any) -> None:
    """Wait until the device has produced every leaf of ``x``.

    ``jax.block_until_ready`` is the sync: on one TPU v5 lite a chained
    4096^2 bf16 matmul timed with it reads 192 TFLOP/s, under the 197 peak
    and the same as timing with a device-to-host fetch (chip run, PR 21).
    """
    jax.block_until_ready(x)


class Profiler:
    """``jax.profiler`` wrapper: programmatic traces + live capture server."""

    def __init__(self, trace_dir: str | Path | None = None) -> None:
        self.trace_dir = str(trace_dir) if trace_dir else None
        self._active = False

    def start_server(self, port: int = 9999) -> None:
        """Expose the live profiling endpoint (TensorBoard 'capture profile')."""
        jax.profiler.start_server(port)

    def start(self) -> None:
        if self.trace_dir and not self._active:
            jax.profiler.start_trace(self.trace_dir)
            self._active = True

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    def trace_steps(self, step_fn, *args, num_steps: int = 3):
        """Trace ``num_steps`` invocations of ``step_fn`` and return the last
        result — the standard "capture a few hot steps" workflow."""
        self.start()
        try:
            out = None
            for _ in range(num_steps):
                out = step_fn(*args)
            jax.block_until_ready(out)
            return out
        finally:
            self.stop()

    def __enter__(self) -> "Profiler":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


class StepTimer:
    """Per-step timing under async dispatch, with summary percentiles.

    Call :meth:`tick` once per training step with the step's output (any
    pytree on device). Every ``sync_every`` steps it blocks on the output and
    attributes the elapsed wall time evenly to the intervening steps — cheap
    enough to leave on (one host sync per window), accurate enough for
    images/sec and latency percentiles.
    """

    def __init__(self, sync_every: int = 10) -> None:
        self.sync_every = sync_every
        self.durations_s: list[float] = []
        self._window_start: float | None = None
        self._pending = 0
        self._last_output: Any = None

    def _close_window(self) -> None:
        host_sync(self._last_output)
        now = time.perf_counter()
        per_step = (now - self._window_start) / self._pending
        self.durations_s.extend([per_step] * self._pending)
        self._window_start = now
        self._pending = 0

    def tick(self, step_output: Any) -> None:
        if self._window_start is None:
            # First call: sync so the window starts from an idle device.
            host_sync(step_output)
            self._window_start = time.perf_counter()
            return
        self._pending += 1
        self._last_output = step_output
        if self._pending >= self.sync_every:
            self._close_window()

    def summary(self, items_per_step: int | None = None) -> dict[str, float]:
        """Latency percentiles (+ throughput when ``items_per_step`` given).

        Flushes the trailing partial window first (one extra host sync), so
        short epochs — fewer steps than ``sync_every`` — still report stats.
        """
        if self._pending:
            self._close_window()
        if not self.durations_s:
            return {}
        d = sorted(self.durations_s)
        out = {
            "steps_timed": float(len(d)),
            "step_ms_p50": statistics.median(d) * 1e3,
            "step_ms_p90": d[int(0.9 * (len(d) - 1))] * 1e3,
            "step_ms_p95": d[int(0.95 * (len(d) - 1))] * 1e3,
            "step_ms_max": d[-1] * 1e3,
        }
        if items_per_step:
            mean = sum(d) / len(d)
            out["items_per_s"] = items_per_step / mean
            out["items_per_s_per_device"] = (
                out["items_per_s"] / jax.device_count()
            )
        return out


def measure_collective_latency(
    mesh: jax.sharding.Mesh,
    *,
    num_floats: int = 1 << 20,
    axis: str = "data",
    trials: int = 10,
) -> dict[str, float]:
    """Time a gradient-sized all-reduce over ``axis`` — the step-latency
    metric the reference never measures (its analog hot path: the NCCL
    all-reduce inside DDP backward, ``pytorch/resnet/main.py:131``).

    Returns mean/min milliseconds and the implied algorithmic bandwidth.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    if n == 1:
        # bus_gbps is 0.0, not inf: no bytes cross any link on a 1-device
        # axis, and inf would serialize as invalid JSON downstream (bench.py
        # prints this dict).
        return {"all_reduce_ms_mean": 0.0, "all_reduce_ms_min": 0.0,
                "axis_size": 1.0, "bus_gbps": 0.0}

    @jax.jit
    def allreduce(x):
        # Reduce to one scalar so the timing fetch is tiny. Summing the WHOLE
        # result (not a slice) keeps the full-buffer collective live — a
        # sliced dependency could let XLA shrink the psum to 8 floats.
        from deeplearning_mpi_tpu.runtime.compat import shard_map

        reduced = shard_map(
            lambda s: jax.lax.psum(s, axis),
            mesh=mesh,
            in_specs=P(axis), out_specs=P(),
            check_vma=False,
        )(x)
        return jnp.sum(reduced)

    x = jax.device_put(
        jnp.ones((n * num_floats,), jnp.float32),
        NamedSharding(mesh, P(axis)),
    )
    host_sync(allreduce(x))  # compile + warm
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        host_sync(allreduce(x))
        times.append(time.perf_counter() - t0)
    mean = sum(times) / len(times)
    # Ring all-reduce moves 2*(n-1)/n of the buffer per device.
    bytes_moved = 2 * (n - 1) / n * num_floats * 4
    return {
        "all_reduce_ms_mean": mean * 1e3,
        "all_reduce_ms_min": min(times) * 1e3,
        "axis_size": float(n),
        "bus_gbps": bytes_moved / min(times) / 1e9,
    }


def nan_debug_mode(enable: bool = True) -> None:
    """Toggle ``jax_debug_nans`` — the framework's race/NaN-detection analog
    (``SURVEY.md`` §5.2: the reference's only guard is a per-batch isfinite
    check, ``pytorch/unet/train.py:186-188``). With it on, the first NaN-
    producing op raises with a stack trace instead of poisoning the run."""
    jax.config.update("jax_debug_nans", enable)
