"""The serving engine's step from inside: the ``serve/`` host spans that
``ServingEngine.step`` writes into the profiler's own trace (one ``serve/step``
a step, one child a phase; ``deeplearning_mpi_tpu/serving/engine.py``), read
beside the device's operations on the same clock.

``what: host_ms_per_step`` -- per whole step, its duration less the children
named in ``minus`` (the blocking fetches): the host's own work in a step.
``what: span_ms`` -- a statistic of the durations of ``span``; with
``steps_with`` only in (or, for ``serve/step`` itself, of) the steps that
hold at least one child of that name.
``what: idle_share`` -- device idle time (gaps of 50 us and more, the rule of
``TraceSummary.idle_gaps``) that falls in ``spans``, over the traced slice.
One idle stretch between two decode programs is ~3 ms long and covers the end
of a token fetch, ``serve/retire``, ``serve/gauges``, the caller's loop and most
of the next launch, so a stretch is shared out by overlap, not given whole to
the span at its middle. The shares of all spans, of the steps' own time, of
the caller's loop and of the small gaps add up to the device's idle share.
``log_table`` prints the whole table to standard error.

The device plane's clock is not the host plane's: on the v5e the two differ by
0.2 to 2 ms, anew in every profiler session (a program "starts" on the device
over a millisecond before the host has issued it). ``clock_offset`` finds the
shift from what has to hold -- a program starts after the runtime's
``tpu::System::Execute`` began and ends before its ``=>Done`` -- and the idle
stretches are shifted by it before they are shared out.

``benchmark/trace.py`` keeps only ``bench/`` and ``trainer/`` host events, so
this reader opens the newest ``.xplane.pb`` under ``run.trace_dir`` itself,
once a run. A program without the spans (or a slice with fewer than ten whole
steps) gives None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from collections import defaultdict
from pathlib import Path
from typing import Any, Iterable, Sequence

from benchmark import trace as device_trace
from benchmark.stats import quantile

HostEvent = tuple[str, float, float, dict[str, Any]]  # name, start seconds, duration seconds, labels

PREFIX, STEP = "serve/", "serve/step"
OUTSIDE, SMALL = "outside serve/step", "gaps_under_50_us"
ISSUED, DONE = "tpu::System::Execute", "tpu::System::Execute=>Done"  # the runtime's own host events, one each a program
MIN_STEPS = 10


@dataclasses.dataclass
class Step:
    """One whole ``serve/step`` and the ``serve/`` spans inside it, by start."""

    start: float
    seconds: float
    labels: dict[str, Any]
    children: list[HostEvent]

    def child_seconds(self, names: Iterable[str]) -> float:
        names = set(names)
        return sum(d for n, _, d, _ in self.children if n in names)

    def count(self, name: str) -> int:
        return sum(n == name for n, _, _, _ in self.children)


def whole_steps(threads: Iterable[Sequence[HostEvent]]) -> list[Step]:
    """The steps of every host thread, by start. The profiler records a span
    only if it began and ended inside the session, so a step cut by either
    edge of the slice is not there, and what is left of its children has no
    parent: those are dropped."""
    out = []
    for events in threads:
        events = sorted(events, key=lambda e: (e[1], -e[2]))
        parents = [e for e in events if e[0] == STEP]
        starts = [e[1] for e in parents]
        steps = [Step(s, d, labels, []) for _, s, d, labels in parents]
        for e in events:
            if e[0] == STEP:
                continue
            i = bisect.bisect_right(starts, e[1]) - 1
            if i >= 0 and e[1] + e[2] <= steps[i].start + steps[i].seconds:
                steps[i].children.append(e)
        out.extend(steps)
    return sorted(out, key=lambda s: s.start)


def _paired(host: Sequence[float], device: Sequence[float]) -> list[float]:
    """``host[i] - device[i]`` of two sequences of the same programs in order,
    one of which may lack up to two at either end (the edges of the slice):
    under the shift that makes the differences most alike."""
    best: tuple[float, list[float]] | None = None
    for shift in range(-2, 3):
        diffs = [h - d for h, d in zip(host[max(shift, 0):], device[max(-shift, 0):])]
        if len(diffs) >= MIN_STEPS:
            width = quantile(diffs, 0.9) - quantile(diffs, 0.1)
            if best is None or width < best[0]:
                best = (width, diffs)
    return best[1] if best else []


def clock_offset(issued: Sequence[float], done: Sequence[float], modules: Sequence[tuple[str, float, float]]) -> tuple[float, float] | None:
    """The least and the most that may be added to the device's times to put
    them on the host's clock: program k starts no earlier than the host began
    to issue it (``issued[k]``) and ends no later than the host saw it done
    (``done[k]``). None where the runtime's events are missing or disagree."""
    modules = sorted(modules, key=lambda m: m[1])
    least = _paired(sorted(issued), [s for _, s, _ in modules])
    most = _paired(sorted(done), [s + d for _, s, d in modules])
    if not least or not most or max(least) > min(most):
        return None
    return max(least), min(most)


def idle_by_span(steps: Sequence[Step], ops: Sequence[tuple[str, float, float]], offset: float = 0.0) -> dict[str, float]:
    """Idle seconds of one device by what the engine was doing. Each idle
    stretch (``offset`` added to the device's times) is shared out by overlap:
    to the child spans it covers, to ``serve/step`` where only the step itself
    does (its self time), to ``OUTSIDE`` where no whole step does."""
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    starts = [s.start for s in steps]
    by: dict[str, float] = defaultdict(float)
    for start, dur in device_trace.gaps([(s, d) for _, s, d in ops], lo, hi):
        if dur < device_trace.SMALL_GAP_S:
            by[SMALL] += dur
            continue
        a, b = start + offset, start + offset + dur
        left = dur
        for st in steps[max(bisect.bisect_right(starts, a) - 1, 0):bisect.bisect_left(starts, b)]:
            inside = min(b, st.start + st.seconds) - max(a, st.start)
            if inside <= 0:
                continue
            for n, s, d, _ in st.children:
                part = max(0.0, min(b, s + d) - max(a, s))
                by[n] += part
                inside -= part
                left -= part
            by[STEP] += inside
            left -= inside
        by[OUTSIDE] += left
    return {n: v for n, v in by.items() if v > 0}


def table(steps: Sequence[Step], idle: dict[str, float], window_s: float, groups: dict[str, Sequence[str]], clocks: str = "") -> str:
    """Every span with count, total, median and the device idle under it; the
    idle share split by ``groups`` and the rest; steps by chunks carried."""
    durations: dict[str, list[float]] = defaultdict(list)
    for st in steps:
        durations[STEP].append(st.seconds)
        for n, _, d, _ in st.children:
            durations[n].append(d)
    tiled = quantile([st.child_seconds(durations) / st.seconds for st in steps], 0.5)
    lines = [
        f"serve/ spans: {len(steps)} whole steps in a slice of {window_s:.3f} s; children cover {100 * tiled:.2f}% of a step (median)",
        *([f"  {clocks}"] if clocks else []),
        f"  {'span':<26}{'count':>7}{'total_ms':>11}{'p50_ms':>10}{'idle_ms':>10}",
    ]
    for name in sorted(durations, key=lambda n: (n != STEP, -sum(durations[n]))):
        d = durations[name]
        lines.append(f"  {name + (' (idle: self)' if name == STEP else ''):<26}{len(d):>7}{1e3 * sum(d):>11.2f}"
                     f"{1e3 * quantile(d, 0.5):>10.3f}{1e3 * idle.get(name, 0.0):>10.2f}")
    for name in (OUTSIDE, SMALL):
        lines.append(f"  {name:<54}{1e3 * idle.get(name, 0.0):>10.2f}")
    share = {g: 100 * sum(idle.get(n, 0.0) for n in names) / window_s for g, names in groups.items()}
    grouped = {n for names in groups.values() for n in names}
    rest = {n: 100 * s / window_s for n, s in idle.items() if n not in grouped}
    lines.append(
        "  device idle " + " + ".join(f"{g} {v:.3f}%" for g, v in share.items()) + f" + rest {sum(rest.values()):.3f}% ("
        + ", ".join(f"{n} {v:.3f}%" for n, v in sorted(rest.items(), key=lambda kv: -kv[1]))
        + f") = {sum(share.values()) + sum(rest.values()):.3f}% of the slice"
    )
    by_chunks: dict[int, list[float]] = defaultdict(list)
    for st in steps:
        by_chunks[st.count("serve/prefill_launch")].append(st.seconds)
    lines.append("  steps by prefill chunks carried: " + ", ".join(
        f"{k}: {100 * len(v) / len(steps):.1f}% ({len(v)}, p50 {1e3 * quantile(v, 0.5):.2f} ms)" for k, v in sorted(by_chunks.items())
    ))
    return "\n".join(lines)


@dataclasses.dataclass
class HostSide:
    """What one trace's host plane says: the engine's whole steps, and when
    the runtime issued each program and saw it done (seconds)."""

    steps: list[Step]
    issued: list[float]
    done: list[float]


@functools.lru_cache(maxsize=2)
def _parsed(path: str) -> HostSide:
    from jax.profiler import ProfileData

    threads, issued, done = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    events.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9, {k: v for k, v in e.stats}))
                elif e.name in (ISSUED, DONE):
                    (issued if e.name == ISSUED else done).append(e.start_ns * 1e-9)
            threads.append(events)
    return HostSide(whole_steps(threads), issued, done)


def host_side(trace_dir: Path | str) -> HostSide:
    """The host plane of the newest ``.xplane.pb`` under ``trace_dir`` (parsed
    once however many metrics ask); empty where there is no trace."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return _parsed(str(found[-1])) if found else HostSide([], [], [])


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    host = host_side(run.trace_dir)
    steps = host.steps
    if len(steps) < MIN_STEPS:
        return None
    what = spec["what"]
    if what == "host_ms_per_step":
        return 1e3 * quantile([st.seconds - st.child_seconds(spec["minus"]) for st in steps], 0.5)
    if what == "span_ms":
        if "steps_with" in spec:
            steps = [st for st in steps if st.count(spec["steps_with"])]
        if spec["span"] == STEP:
            values = [st.seconds for st in steps]
        else:
            values = [d for st in steps for n, _, d, _ in st.children if n == spec["span"]]
        if not values:
            return None
        return 1e3 * quantile(values, float(spec["stat"].lstrip("p")) / 100.0)
    device = trace.devices[0]
    window = clock_offset(host.issued, host.done, device.modules)
    offset = sum(window) / 2 if window else 0.0
    idle = idle_by_span(steps, device.ops, offset)
    if spec.get("log_table"):
        from benchmark.run import log

        clocks = (
            f"device clock {1e3 * offset:+.3f} ms = host clock (between {1e3 * window[0]:+.3f} and {1e3 * window[1]:+.3f}: "
            f"{len(device.modules)} programs start after the host issued them and end before it saw them done)"
            if window else "device clock taken as the host clock: the runtime's issue and completion events are missing or disagree"
        )
        log(table(steps, idle, trace.window_s, spec["log_table"], clocks))
    return 100.0 * sum(idle.get(n, 0.0) for n in spec["spans"]) / trace.window_s
