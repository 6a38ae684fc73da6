"""From a profiler trace (``.xplane.pb``) to device busy and idle time, time
per operation and per program, exposed collective time and what the host was
doing in the idle gaps.

The parsing follows ``tools/profile_lm.py`` (device lanes, operation events,
the step and module lanes left out of the sums) but reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` instead of the perfetto JSON. The arithmetic
works on plain ``(start, duration)`` lists, so it is tested without a trace too.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Iterable, Sequence

Event = tuple[str, float, float]  # name, start seconds, duration seconds

COLLECTIVE_RE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?\b")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
SMALL_GAP_S = 50e-6


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of ``(start, duration)`` intervals."""
    total, end = 0.0, float("-inf")
    for start, dur in sorted(intervals):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle ``(start, duration)`` stretches of ``[lo, hi]`` that no interval covers."""
    out, end = [], lo
    for start, dur in sorted(intervals):
        if start > end:
            out.append((end, min(start, hi) - end))
        end = max(end, start + dur)
    if hi > end:
        out.append((end, hi - end))
    return [g for g in out if g[1] > 0]


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``fusion.12 bf16[8,128] fusion``."""
    m = re.match(r"%?([\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])[^ ]* ?.*? ([\w\-]+)\(", hlo)
    if not m:
        return hlo[:80]
    return f"{m.group(1)} {m.group(2).lstrip('(')} {m.group(3)}"


@dataclasses.dataclass
class DeviceTrace:
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class TraceSummary:
    """What one traced slice says. Times in seconds; per-device numbers are
    averaged over the devices that ran an operation."""

    devices: list[DeviceTrace]
    host: list[Event]
    window_s: float
    busy_s: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the operations whose short name (``short_name``)
        matches ``pattern``, a device's mean."""
        rx = re.compile(pattern)
        return sum(d for dev in self.devices for n, _, d in dev.ops if rx.search(short_name(n))) / len(self.devices)

    def module_durations(self, pattern: str) -> list[float]:
        rx = re.compile(pattern)
        return [d for dev in self.devices for n, _, d in dev.modules if rx.search(n)]

    def top_ops(self, k: int = 10) -> list[list[Any]]:
        by: dict[str, float] = defaultdict(float)
        for dev in self.devices:
            for n, _, d in dev.ops:
                by[short_name(n)] += d / len(self.devices)
        return [[n, s] for n, s in sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def exposed_collective_seconds(self) -> float:
        """Collective time no compute overlaps: on the operation lane a device
        runs one operation at a time, so a collective (or the ``-done`` an
        asynchronous one waits in) that occupies the lane is exposed."""
        return self.op_seconds(COLLECTIVE_RE.pattern)

    def idle_gaps(self, k: int = 10) -> list[list[Any]]:
        """Idle time of the first device by what the host was doing: the
        innermost host annotation that covers the middle of each gap."""
        dev = self.devices[0]
        lo = min(s for _, s, _ in dev.ops)
        hi = max(s + d for _, s, d in dev.ops)
        by: dict[str, float] = defaultdict(float)
        for start, dur in gaps([(s, d) for _, s, d in dev.ops], lo, hi):
            if dur < SMALL_GAP_S:
                by["gaps_under_50_us"] += dur
                continue
            mid = start + dur / 2
            cover = [(d, n) for n, s, d in self.host if s <= mid <= s + d]
            by[min(cover)[1] if cover else "no_host_annotation"] += dur
        return [[n, s] for n, s in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def summarize(devices: Sequence[DeviceTrace], host: Sequence[Event] = ()) -> TraceSummary:
    ran = [d for d in devices if d.ops]
    if not ran:
        raise ValueError("no operation ran on a device in the traced slice")
    lo = min(s for d in ran for _, s, _ in d.ops)
    hi = max(s + dur for d in ran for _, s, dur in d.ops)
    busy = sum(union_seconds((s, dur) for _, s, dur in d.ops) for d in ran) / len(ran)
    return TraceSummary(list(ran), list(host), hi - lo, busy)


HOST_PREFIXES = ("bench/", "trainer/")


def start(path: Path | str) -> None:
    """Start the profiler with Python call tracing off (it slows the host
    loop that is being measured); the program's and the benchmark's own
    annotations are host-tracer events and stay."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)


def read(path: Path | str) -> TraceSummary:
    """Reduce the newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if re.match(r"^/device:TPU:\d+$", plane.name):
            dev = DeviceTrace([], [])
            for line in plane.lines:
                if line.name not in (OP_LINE, MODULE_LINE):
                    continue
                dest = dev.ops if line.name == OP_LINE else dev.modules
                for e in line.events:
                    dest.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return summarize(devices, host)
