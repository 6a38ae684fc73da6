"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. Refuses anything but a TPU with the chips the cell
asks for, keeps the compile cache inside the checkout, warms up the cell's own
shapes (set-up), measures for ``--seconds``, then checks what the timed path
produced against the plain reference. Diagnostics go to standard error; the
last line of standard output is the one JSON object of the contract.
"""

from __future__ import annotations

import os
import sys
import time

_T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable  # noqa: E402

if __package__ in (None, ""):  # run as a script: make ``benchmark`` importable
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import costs  # noqa: E402
from benchmark.manifest import ROOT, Manifest  # noqa: E402


def _process_start() -> float:
    """``time.monotonic()`` of this process's start, from /proc where it has one."""
    try:
        ticks = float(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 600:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


class SetupClock:
    """Set-up, from the start of the process to the start of the window (or
    of the lead before it), broken down by phase."""

    def __init__(self, start: float) -> None:
        self.start = start
        self.phases: dict[str, float] = {}
        self._mark = start
        self.total: float | None = None

    def phase(self, name: str) -> None:
        """Everything since the last mark was ``name``."""
        now = time.monotonic()
        self.phases[name] = self.phases.get(name, 0.0) + now - self._mark
        self._mark = now

    def done(self) -> float:
        self.phase("other")
        self.total = time.monotonic() - self.start
        return self.total

    def line(self) -> str:
        parts = ", ".join(f"{k} {v:.2f}" for k, v in self.phases.items())
        return f"setup_s {self.total:.2f} = {parts}"


@dataclasses.dataclass
class Run:
    """What a driver is handed, and what it hands back."""

    manifest: Manifest
    cell: dict[str, Any]
    config: dict[str, Any]
    traffic: dict[str, Any]
    limits: dict[str, float]
    seed: int
    seconds: float
    trace: bool
    setup: SetupClock
    trace_dir: Path
    # filled by the driver
    end_to_end: dict[str, float] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: list[tuple[str, float, float]] = dataclasses.field(default_factory=list)
    spans: dict[str, list[float]] = dataclasses.field(default_factory=dict)
    counters: dict[str, float] = dataclasses.field(default_factory=dict)
    work: list[dict[str, Any]] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    after: dict[str, float] = dataclasses.field(default_factory=dict)
    devices: list[Any] = dataclasses.field(default_factory=list)
    compiles: Any = None

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def check(self, name: str, value: float) -> None:
        """Compare one number with its limit, which the cell's file has to state."""
        if name not in self.limits:
            raise KeyError(f"cells/{self.cell['name']}.json states no limit for {name}")
        self.checks.append((name, float(value), float(self.limits[name])))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim and v == v for _, v, lim in self.checks)


def memory_peak(devices: list[Any]) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend keeps no count)."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices))


class CompileWatch:
    """Counts compilations and compile-cache loads, so that one inside the
    window makes the run not correct."""

    EVENTS = ("/jax/core/compile/backend_compile_duration", "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.count += 1
            self.seconds += seconds


def find_chips(cell: dict[str, Any]) -> list[Any]:
    """The TPU devices this cell runs on, or SystemExit(2) with no result."""
    from deeplearning_mpi_tpu.runtime import bootstrap

    bootstrap.select_platform("tpu")  # a TPU that fails to come up is an error, not a CPU run
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        log(f"no accelerator: {err}")
        raise SystemExit(2)
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); found {len(devices)} x {devices[0].platform}")
        raise SystemExit(2)
    return devices[: cell["chips"]]


def per_layer(run: Run, summary: Any, device_kind: str) -> dict[str, dict[str, Any]]:
    """Each per-layer metric of this cell through its own reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in run.manifest.cell_per_layer(run.cell["name"]):
        spec = run.manifest.metric_file(name)
        value = run.manifest.reader(spec["reader"]).read(run, summary, spec, device_kind)
        if value is None:
            log(f"per-layer {name}: its reader found nothing to read")
            continue
        if spec["unit"] == "%" and ("roofline" in name or "mfu" in name) and value > 100.0:
            log(f"WARNING {name} reads {value:.1f}%: the operations or bytes are counted too high, or the time leaves out work")
        out[name] = {"value": value, "unit": spec["unit"]}
    return out


def open_run(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT, chips: Callable[[dict], list] = find_chips) -> Run | None:
    """The manifest read, the cell found, the chips taken: everything before
    the driver. None where BENCHMARK.json has no such cell."""
    setup = SetupClock(_process_start())
    manifest = Manifest(root)
    if workload not in manifest.cells:
        log(f"no cell named {workload!r} in BENCHMARK.json")
        return None
    cell = manifest.cells[workload]
    trace_dir = root / ".bench_trace" / cell["name"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]), traffic=manifest.traffic(cell["traffic"]),
        limits=manifest.cell_file(cell["name"])["limits"], seed=seed, seconds=seconds,
        trace=trace, setup=setup, trace_dir=trace_dir,
    )
    setup.phase("imports")
    run.devices = chips(cell)
    import jax

    from deeplearning_mpi_tpu.compiler import cache as program_cache

    setup.phase("program_imports")
    run.compiles = CompileWatch()
    first = run.devices[0]
    log(
        f"{cell['name']}: platform {first.platform}, device_kind {first.device_kind!r}, {len(run.devices)} of "
        f"{jax.device_count()} device(s); seed {seed}; compile cache {program_cache.cache_dir()}"
    )
    return run


def run_cell(argv: list[str] | None = None, *, root: Path = ROOT, chips: Callable[[dict], list] = find_chips) -> tuple[int, dict[str, Any] | None]:
    """The whole of a run but the printing of its last line. Tests pass their
    own ``root`` (data files) and ``chips`` (skipping the look for a TPU)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = open_run(args.workload, args.seed, args.seconds, bool(args.trace), root=root, chips=chips)
    if run is None:
        return 2, None
    manifest, cell, setup, devices, trace_dir = run.manifest, run.cell, run.setup, run.devices, run.trace_dir
    kind = devices[0].device_kind
    manifest.driver(run.traffic["driver"]).run(run)

    device: dict[str, Any] = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result: dict[str, Any] = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed}
    if run.trace:
        from benchmark import trace as trace_mod

        t0 = time.monotonic()
        summary = trace_mod.read(trace_dir)
        costs.peak(kind)  # an unknown device kind is an error before any share is worked out
        result["metrics"] = per_layer(run, summary, kind)
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.top_ops(10), "idle_gaps": summary.idle_gaps(10)}
        run.after["trace_reading"] = time.monotonic() - t0
    else:
        names = manifest.cell_end_to_end(cell["name"])
        values = {**run.end_to_end, "setup_s": setup.total}
        result["metrics"] = {n: {"value": values[n], "unit": manifest.end_to_end[n]["unit"]} for n in names}
        result["device"] = device
    log(setup.line())
    log("after the window (not set-up): " + ", ".join(f"{k} {v:.2f}" for k, v in run.after.items()))
    result["check"] = {n: {"value": v, "limit": lim, "ok": bool(v <= lim)} for n, v, lim in run.checks}
    for n, v, lim in run.checks:
        log(f"check {n} = {v:.6g} (limit {lim:.6g}) {'ok' if v <= lim else 'NOT OK'}")
    log(f"correct = {run.correct}")
    return 0, result


def main(argv: list[str] | None = None) -> int:
    code, result = run_cell(argv)
    shutil.rmtree(ROOT / ".bench_trace", ignore_errors=True)  # a trace is read once; nothing is left on the disk
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
