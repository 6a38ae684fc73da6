"""Where the chip waits between two serving programs: device idle time given
to the innermost host span over it, down inside the launch and the fetch.

``deeplearning_mpi_tpu/serving/launch.py`` writes ``launch/prep`` (the host
builds the step's inputs), ``launch/h2d`` (their transfers) and
``launch/dispatch`` (the call into the program) inside every
``serve/*_launch`` span, and ``fetch/ready`` (the wait for the program) and
``fetch/d2h`` (the copy back) inside every ``serve/*_fetch``. This reader
takes the host plane's ``serve/``, ``launch/`` and ``fetch/`` events, nests
them by containment on each thread under the whole ``serve/step`` spans (what
lies outside one, cut by an edge of the slice, is dropped), puts the device
on the host's clock with ``serve_spans.clock_offset`` and gives each part of
an idle stretch of 50 us or more to the innermost span that covers it: a
span's own idle is what none of its children covers.

``spans``: the share of the slice idle under those names, wherever they sit.
``log_tree``: prints the tree (count, total, median, idle under each span and
its own), the shares of its groups beside the rest by name (the spans' own
idle, ``outside serve/step``, ``gaps_under_50_us``), which add up to the
device's idle share, and where the runtime issued each program
(``tpu::System::Execute``). A program without ``launch/`` spans, or a slice
with fewer than ten whole steps, gives None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from collections import defaultdict
from pathlib import Path
from typing import Any, Iterable, Sequence

from benchmark import trace as device_trace
from benchmark.readers.serve_spans import DONE, ISSUED, MIN_STEPS, OUTSIDE, SMALL, STEP, clock_offset
from benchmark.stats import quantile

PREFIXES = ("serve/", "launch/", "fetch/")
LAUNCH, DISPATCH = "launch/", "launch/dispatch"
SpanPath = tuple[str, ...]  # span names from the step down


@dataclasses.dataclass
class Node:
    """One host span and the spans it contains."""

    name: str
    start: float
    seconds: float
    labels: dict[str, Any]
    children: list[Node] = dataclasses.field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def walk(self, path: SpanPath = ()) -> Any:
        """``(path, node)`` of this span and every span below it."""
        path = (*path, self.name)
        yield path, self
        for child in self.children:
            yield from child.walk(path)


def nest(events: Sequence[tuple[str, float, float, dict[str, Any]]]) -> list[Node]:
    """The events of one thread as trees, by start: an event that starts
    before the one open ends is inside it."""
    roots: list[Node] = []
    open_: list[Node] = []
    for name, start, seconds, labels in sorted(events, key=lambda e: (e[1], -e[2])):
        node = Node(name, start, seconds, labels)
        while open_ and start >= open_[-1].end:
            open_.pop()
        (open_[-1].children if open_ else roots).append(node)
        open_.append(node)
    return roots


def whole_steps(threads: Iterable[Sequence[tuple[str, float, float, dict[str, Any]]]]) -> list[Node]:
    """The ``serve/step`` trees of every thread, by start; what no whole step
    holds (a step cut by an edge of the slice, or its orphaned children)
    is left out."""
    return sorted((root for events in threads for root in nest(events) if root.name == STEP), key=lambda s: s.start)


def _share(node: Node, a: float, b: float, path: SpanPath, by: dict[SpanPath, float]) -> float:
    """Of ``[a, b]``, what ``node`` covers, given to the innermost spans
    under it by path; returns what it covers."""
    lo, hi = max(a, node.start), min(b, node.end)
    if hi <= lo:
        return 0.0
    path = (*path, node.name)
    own = hi - lo
    for child in node.children:
        own -= _share(child, lo, hi, path, by)
    by[path] += own
    return hi - lo


def idle_tree(steps: Sequence[Node], ops: Sequence[tuple[str, float, float]], offset: float = 0.0) -> dict[SpanPath, float]:
    """Idle seconds of one device by the innermost span over them (its path
    from the step down), ``(OUTSIDE,)`` where no whole step is, ``(SMALL,)``
    for the gaps under 50 us; ``offset`` is added to the device's times.
    The values add up to the device's idle time."""
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    starts = [s.start for s in steps]
    by: dict[SpanPath, float] = defaultdict(float)
    for start, dur in device_trace.gaps([(s, d) for _, s, d in ops], lo, hi):
        if dur < device_trace.SMALL_GAP_S:
            by[(SMALL,)] += dur
            continue
        a, b = start + offset, start + offset + dur
        left = dur
        for st in steps[max(bisect.bisect_right(starts, a) - 1, 0):bisect.bisect_left(starts, b)]:
            left -= _share(st, a, b, (), by)
        by[(OUTSIDE,)] += left
    return {p: v for p, v in by.items() if v > 0}


def _innermost(steps: Sequence[Node], starts: Sequence[float], t: float) -> SpanPath | None:
    """The path of the innermost span over the instant ``t``; None outside
    every whole step."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0 or t > steps[i].end:
        return None
    node, path = steps[i], (steps[i].name,)
    while True:
        inside = [c for c in node.children if c.start <= t <= c.end]
        if not inside:
            return path
        node = inside[0]
        path = (*path, node.name)


def table(steps: Sequence[Node], idle: dict[SpanPath, float], window_s: float, groups: dict[str, Sequence[str]],
          issued: Sequence[float] = (), clocks: str = "") -> str:
    """The tree with count, total, median, idle under each span and its own;
    the shares of ``groups`` (by span name) and the rest by name; where the
    runtime issued the programs of the slice."""
    durations: dict[SpanPath, list[float]] = defaultdict(list)
    for st in steps:
        for path, node in st.walk():
            durations[path].append(node.seconds)
    under = defaultdict(float)
    for path, v in idle.items():
        for k in range(1, len(path) + 1):
            under[path[:k]] += v
    spans = sum(map(len, durations.values()))
    lines = [
        f"serve/ launch/ fetch/ tree: {len(steps)} whole steps in a slice of {window_s:.3f} s, {spans / len(steps):.2f} spans a step",
        *([f"  {clocks}"] if clocks else []),
        f"  {'span':<40}{'count':>7}{'total_ms':>11}{'p50_ms':>9}{'idle_ms':>10}{'own_idle_ms':>12}",
    ]

    def rows(prefix: SpanPath) -> None:
        below = [p for p in durations if len(p) == len(prefix) + 1 and p[:len(prefix)] == prefix]
        for p in sorted(below, key=lambda p: -sum(durations[p])):
            d = durations[p]
            lines.append(f"  {'  ' * len(prefix) + p[-1]:<40}{len(d):>7}{1e3 * sum(d):>11.2f}{1e3 * quantile(d, 0.5):>9.3f}"
                         f"{1e3 * under[p]:>10.2f}{1e3 * idle.get(p, 0.0):>12.2f}")
            rows(p)

    rows(())
    share = {g: 100 * sum(v for p, v in idle.items() if p[-1] in names) / window_s for g, names in groups.items()}
    grouped = {n for names in groups.values() for n in names}
    rest: dict[str, float] = defaultdict(float)
    for p, v in idle.items():
        if p[-1] not in grouped:
            rest[p[-1] + (" (own)" if p[-1] not in (OUTSIDE, SMALL) else "")] += 100 * v / window_s
    lines.append(
        "  device idle " + " + ".join(f"{g} {v:.3f}%" for g, v in share.items()) + f" + rest {sum(rest.values()):.3f}% ("
        + ", ".join(f"{n} {v:.3f}%" for n, v in sorted(rest.items(), key=lambda kv: -kv[1]))
        + f") = {sum(share.values()) + sum(rest.values()):.3f}% of the slice"
    )
    launches = [p for p in under if len(p) == 2 and p[1].endswith("_launch")]
    held = sum(v for p, v in idle.items() if len(p) > 2 and p[1].endswith("_launch") and p[2].startswith(LAUNCH))
    total = sum(under[p] for p in launches)
    if total:
        lines.append(f"  of the {100 * total / window_s:.3f}% idle under {', '.join(sorted(p[1] for p in launches))}: "
                     f"{100 * held / window_s:.3f}% under their launch/ children ({100 * held / total:.1f}%)")
    starts = [s.start for s in steps]
    where: dict[str, int] = defaultdict(int)
    for t in issued:
        path = _innermost(steps, starts, t)
        if path is not None:
            where[" > ".join(path[1:]) or STEP] += 1
    n = sum(where.values())
    if n:
        inside = sum(k for p, k in where.items() if p.endswith(DISPATCH))
        lines.append(f"  programs issued inside whole steps: {n}, {inside} ({100 * inside / n:.1f}%) inside a {DISPATCH}; "
                     + ", ".join(f"{p} {k}" for p, k in sorted(where.items(), key=lambda kv: -kv[1])))
    return "\n".join(lines)


@dataclasses.dataclass
class HostSide:
    """The engine's whole steps as trees, and when the runtime issued each
    program and saw it done (seconds)."""

    steps: list[Node]
    issued: list[float]
    done: list[float]

    def has(self, prefix: str) -> bool:
        return any(node.name.startswith(prefix) for st in self.steps for _, node in st.walk())


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
                if e.name.startswith(PREFIXES):
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
    if len(host.steps) < MIN_STEPS or not host.has(LAUNCH):
        return None
    device = trace.devices[0]
    window = clock_offset(host.issued, host.done, device.modules)
    offset = sum(window) / 2 if window else 0.0
    idle = idle_tree(host.steps, device.ops, offset)
    if spec.get("log_tree"):
        from benchmark.run import log

        clocks = (f"device clock {1e3 * offset:+.3f} ms = host clock" if window
                  else "device clock taken as the host clock: the runtime's issue and completion events are missing or disagree")
        log(table(host.steps, idle, trace.window_s, spec["log_tree"], sorted(host.issued), clocks))
    return 100.0 * sum(v for p, v in idle.items() if p[-1] in spec["spans"]) / trace.window_s
