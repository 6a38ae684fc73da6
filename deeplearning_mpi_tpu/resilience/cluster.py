"""Unified supervision core shared by the pod and fleet supervisors.

ROADMAP item 3 observed that :mod:`~.pod` (training) and
:mod:`~deeplearning_mpi_tpu.serving.fleet` (serving) grew as two parallel
supervisors with the same bones: per-worker heartbeat aggregation, the
dead/hung/slow classification built on :class:`LivenessTracker`,
SIGKILL+respawn process lifecycle, supervisor-owned chaos fire/recovery
books, and newline-delimited JSON as the only wire format. This module IS
those bones, extracted so both supervisors wrap one core — and so the
Podracer end-state (one control plane repurposing chips between trainer
ranks and serving replicas under load) has a single place to grow from.

What lives here:

- :class:`LivenessTracker` — progress-seq liveness over heartbeat payloads
  (moved verbatim from ``pod.py``; ``pod`` re-exports it for callers).
- :func:`tail_jsonl` — offset-tailing reader for append-only JSONL IPC
  files that consumes only newline-terminated records (moved from
  ``fleet.py``): a mid-write SIGKILL can truncate at most the final,
  unconsumed line.
- :func:`sigkill_group` / :func:`reap` / :func:`kill_and_reap` — the
  process-group teardown contract (workers are spawned with
  ``start_new_session=True``; SIGKILL goes to the whole group).
- :func:`scrub_rendezvous_env` — strip jax distributed-rendezvous vars
  from a child env: a lone process (serving replica, world-of-one pod
  survivor) must never inherit a coordinator address and wait for peers.
- :class:`ClusterSupervisor` — the shared supervisor base: chaos spec
  resolution + injector construction, registry ownership, the heartbeat
  cadence knobs, the per-supervisor JSONL metrics sink, and tracker
  construction. :class:`~.pod.PodSupervisor` keeps the world re-form
  semantics; :class:`~deeplearning_mpi_tpu.serving.fleet.FleetSupervisor`
  keeps the mailbox/router semantics; both are pinned bit-identical by
  ``make pod-smoke`` / ``make fleet-smoke``.
- :class:`SupervisorJournal` / :func:`replay_journal` /
  :func:`next_incarnation` — the control-plane crash-safety layer
  (docs/RESILIENCE.md "Control-plane crash safety"): an append-only
  write-ahead JSONL journal of every supervisor-owned state transition,
  stamped with a monotonic **incarnation id** so a restarted supervisor
  can tell its own records from a dead predecessor's, replay the fleet
  state, and re-adopt orphaned workers instead of killing them.
"""

from __future__ import annotations

import json
import os
import signal
import statistics
import subprocess
import time
from pathlib import Path
from typing import Any, Callable, Iterable, Mapping, MutableMapping, Optional

from deeplearning_mpi_tpu.resilience.faults import ChaosInjector, FaultPlan
from deeplearning_mpi_tpu.resilience.integrity import atomic_write_json
from deeplearning_mpi_tpu.telemetry.registry import JsonlSink, MetricsRegistry

__all__ = [
    "ENV_HEARTBEAT_DIR",
    "ENV_HEARTBEAT_INTERVAL",
    "ENV_INCARNATION",
    "INCARNATION_FILE",
    "JOURNAL_FILE",
    "SUP_INCARNATION",
    "SUP_READOPTED",
    "SUP_REPLAY_S",
    "SUP_RESPAWNED",
    "ClusterSupervisor",
    "LivenessTracker",
    "SupervisorJournal",
    "kill_and_reap",
    "next_incarnation",
    "pid_alive",
    "reap",
    "replay_journal",
    "scrub_rendezvous_env",
    "sigkill_group",
    "tail_jsonl",
]

#: directory workers write per-rank ``heartbeat-{rank}.json`` files into —
#: the supervisor↔worker contract (``utils/config.py::build_observability``
#: switches to this layout when the var is set).
ENV_HEARTBEAT_DIR = "DMT_HEARTBEAT_DIR"
#: heartbeat interval override (seconds) — drills crank it down to 0.2s.
ENV_HEARTBEAT_INTERVAL = "DMT_HEARTBEAT_INTERVAL_S"

#: env vars of the jax distributed-rendezvous contract
#: (``runtime/bootstrap.py``) — scrubbed from lone-process children.
RENDEZVOUS_VARS = ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID")

#: supervisor incarnation id handed to spawned workers — workers echo it in
#: every heartbeat so :class:`LivenessTracker` can reject records written
#: under a dead control plane (stale-incarnation hygiene).
ENV_INCARNATION = "DMT_SUPERVISOR_INCARNATION"
#: persisted monotonic incarnation counter (``atomic_write_json``).
INCARNATION_FILE = "incarnation.json"
#: the write-ahead journal stream name under the supervisor's run dir.
JOURNAL_FILE = "journal.jsonl"

#: control-plane crash-safety metric names (registered in
#: ``telemetry/schema.py``), shared by every supervisor flavour.
SUP_INCARNATION = "supervisor_incarnation"
SUP_READOPTED = "supervisor_readopted_total"
SUP_RESPAWNED = "supervisor_respawned_total"
SUP_REPLAY_S = "supervisor_journal_replay_s"


def pid_alive(pid: int) -> bool:
    """True iff ``pid`` exists and is not a zombie awaiting reap. Signal-0
    probing alone is not enough for orphan re-adoption: a SIGKILLed child
    of a dead supervisor is reparented and reaped, but a zombie of a
    still-dying tree would pass ``kill(pid, 0)`` while being unable to
    serve — so the /proc state is checked when available."""
    if pid <= 0:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    try:
        with open(f"/proc/{pid}/stat") as f:
            # field 3 (after the parenthesized comm, which may hold spaces)
            state = f.read().rpartition(")")[2].split()[0]
        return state != "Z"
    except (OSError, IndexError):
        return True


def next_incarnation(root_dir: Path | str) -> int:
    """Read-bump-persist the monotonic supervisor incarnation counter for
    ``root_dir``. The counter survives supervisor crashes (it is written
    with :func:`atomic_write_json`, so a mid-bump kill leaves either the
    old or the new value, never a torn file) and only ever moves forward:
    every supervisor start — first boot or post-crash restart — owns a
    strictly larger id than every predecessor."""
    root = Path(root_dir)
    root.mkdir(parents=True, exist_ok=True)
    path = root / INCARNATION_FILE
    prev = 0
    try:
        prev = int(json.loads(path.read_text()).get("incarnation", 0))
    except (OSError, ValueError, TypeError, AttributeError):
        prev = 0
    inc = prev + 1
    atomic_write_json(path, {"incarnation": inc, "pid": os.getpid()})
    return inc


class SupervisorJournal:
    """Append-only write-ahead journal of supervisor-owned state
    transitions (replica spawn/ready/retire, request dispatch/completion,
    scale events, brownout stage, chaos fire/recovery).

    Single-writer by construction: exactly one live incarnation holds the
    append handle (``next_incarnation`` fences restarts — a new supervisor
    bumps the counter before opening the stream, and every record carries
    its writer's incarnation so replay can tell the corpses apart). Each
    record is one newline-terminated JSON line, flushed before the action
    it describes is taken (write-ahead), so a reader following the
    :func:`tail_jsonl` discipline sees either a complete record or — after
    a mid-write SIGKILL — no record at all; a torn final line is never
    parsed. That lost-final-record case is safe by design: a journaled
    action that never happened is re-discovered by the orphan probe, and
    an unjournaled action never happened at all.
    """

    def __init__(self, root_dir: Path | str, *, incarnation: int,
                 clock: Callable[[], float] = time.monotonic) -> None:
        root = Path(root_dir)
        root.mkdir(parents=True, exist_ok=True)
        self.path = root / JOURNAL_FILE
        self.incarnation = incarnation
        self._clock = clock
        # Sanctioned single-writer append handle (dmt-lint DMT005 names
        # this class next to JsonlSink): one live incarnation, one stream.
        self._f = (root / "journal.jsonl").open("a", encoding="utf-8")

    def record(self, ev: str, **fields: Any) -> None:
        """Append one journal record. ``ev`` is the transition kind; extra
        fields are the transition payload. Flushed immediately — the
        journal is write-ahead, so the record must be durable against a
        supervisor SIGKILL *before* the action it describes runs."""
        rec = {"inc": self.incarnation, "t": self._clock(), "ev": ev}
        rec.update(fields)
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass


def replay_journal(path: Path | str) -> list[dict]:
    """All complete records of a journal stream, oldest first. Reuses the
    :func:`tail_jsonl` newline-termination discipline, so a final line
    torn by a mid-write supervisor kill is silently dropped rather than
    raising — the write-ahead contract makes that record's action
    un-taken by definition."""
    records, _ = tail_jsonl(Path(path), 0)
    return records


def tail_jsonl(path: Path, offset: int) -> tuple[list[dict], int]:
    """Read the complete JSONL records appended past ``offset``. Only
    newline-terminated lines are consumed — a partial trailing line (the
    writer died mid-write, or the write raced this read) stays unread
    until its newline lands."""
    try:
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read()
    except OSError:
        return [], offset
    end = data.rfind(b"\n")
    if end < 0:
        return [], offset
    chunk = data[: end + 1]
    out = []
    for line in chunk.splitlines():
        if line.strip():
            out.append(json.loads(line))
    return out, offset + len(chunk)


def sigkill_group(proc: subprocess.Popen) -> None:
    """SIGKILL ``proc``'s whole process group (it was spawned with
    ``start_new_session=True``); fall back to killing the process alone
    when the group is already gone or not ours."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        proc.kill()


def reap(proc: subprocess.Popen, timeout_s: float = 10.0) -> None:
    """Wait for ``proc`` to exit, bounded — a SIGKILL'd group should reap
    promptly; if it does not, leave the zombie rather than hang teardown."""
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        pass


def kill_and_reap(proc: subprocess.Popen, timeout_s: float = 10.0) -> None:
    """The single-process teardown: SIGKILL the group iff still running,
    then reap."""
    if proc.poll() is None:
        sigkill_group(proc)
        reap(proc, timeout_s)


def scrub_rendezvous_env(env: MutableMapping[str, str]) -> None:
    """Remove distributed-rendezvous vars from a child env in place: a
    process launched as a world of one (serving replica, lone pod
    survivor) would otherwise wait forever for peers that never come."""
    for k in RENDEZVOUS_VARS:
        env.pop(k, None)


def workers_would_share_tpu(env: Mapping[str, str], workers: int) -> bool:
    """Whether ``workers`` jax processes started on THIS host with ``env``
    would each initialise the TPU. A chip belongs to one process at a time:
    the spawners here hand every worker the same environment and assign no
    chips, so the second worker dies or hangs on the device lock. Decided
    without touching a backend — from ``JAX_PLATFORMS`` when the workers'
    environment sets it, else from the chips jax itself would find."""
    if workers < 2:
        return False
    platforms = env.get("JAX_PLATFORMS", "").strip()
    if platforms:
        return "tpu" in (p.strip().lower() for p in platforms.split(","))
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0


class LivenessTracker:
    """Pod-level liveness view over per-rank heartbeat payloads.

    All stall math uses THIS process's ``clock`` (injectable for tests) and
    timestamps of observed ``progress_seq`` *changes* — never the payload's
    own ``monotonic``/``time`` fields, which belong to another host's clock.

    Three verdicts per rank:

    - **stalled**: no heartbeat file within ``grace_s`` of tracker start
      (worker never came up), no first progress within ``grace_s`` (wedged
      in startup/compile), or no progress change within ``deadline_s``
      after progressing at least once — the hung-collective signature.
    - **straggler**: progressing, but its current progress age exceeds
      ``straggler_factor`` × the median observed inter-progress interval
      across ranks (and is still under the deadline) — slow, not dead.
    - healthy otherwise.

    When ``incarnation`` is set, heartbeat payloads stamped with a
    *different* supervisor incarnation are ignored: a heartbeat file left
    behind by a worker of a dead control plane can have a recent mtime and
    a nonzero ``progress_seq``, and without the fence a restarted
    supervisor would read it as live progress and let a dead rank hide
    behind its own corpse's last words. Workers echo ``ENV_INCARNATION``
    (updated by the re-adoption handshake), so an adopted worker's
    heartbeats become acceptable the moment it acks the new owner.
    """

    def __init__(
        self,
        ranks: Iterable[int],
        *,
        deadline_s: float,
        grace_s: float,
        straggler_factor: float = 4.0,
        clock: Callable[[], float] = time.monotonic,
        incarnation: int | None = None,
    ) -> None:
        self.deadline_s = deadline_s
        self.grace_s = grace_s
        self.straggler_factor = straggler_factor
        self.incarnation = incarnation
        self._clock = clock
        self._start = clock()
        self._ranks = list(ranks)
        self._last_seq: dict[int, Any] = {}
        self._last_change: dict[int, float] = {}
        self._last_step: dict[int, float] = {}
        self._interval_ema: dict[int, float] = {}
        self._seen_progress: set[int] = set()

    def observe(self, rank: int, payload: Mapping[str, Any] | None) -> None:
        """Feed one heartbeat read (``None`` = file missing/unreadable)."""
        if payload is None:
            return
        if self.incarnation is not None:
            inc = payload.get("incarnation")
            if inc is not None and inc != self.incarnation:
                return  # stale-incarnation hygiene: a corpse's heartbeat
        now = self._clock()
        if isinstance(payload.get("step"), (int, float)):
            self._last_step[rank] = float(payload["step"])
        seq = payload.get("progress_seq", payload.get("time"))
        prev = self._last_seq.get(rank)
        if prev is None:
            self._last_seq[rank] = seq
            self._last_change[rank] = now
            if isinstance(seq, (int, float)) and seq and seq > 0:
                # First read already shows training-loop progress (a fast
                # worker beat us to it) — count it as progress, not baseline.
                self._seen_progress.add(rank)
            return
        if seq != prev:
            interval = now - self._last_change[rank]
            if rank in self._seen_progress:
                ema = self._interval_ema.get(rank)
                self._interval_ema[rank] = (
                    interval if ema is None else 0.5 * ema + 0.5 * interval
                )
            self._seen_progress.add(rank)
            self._last_seq[rank] = seq
            self._last_change[rank] = now

    def any_progress(self) -> bool:
        """True once ANY rank's training loop has demonstrably advanced —
        the supervisor's "the re-formed world is alive" signal that closes
        pending chaos recoveries."""
        return bool(self._seen_progress)

    def progress_age_s(self, rank: int) -> float:
        """Seconds (supervisor clock) since ``rank`` last changed state."""
        return self._clock() - self._last_change.get(rank, self._start)

    def stalled(self, rank: int) -> bool:
        if rank not in self._seen_progress:
            # Startup (spawn + import + compile) gets the grace window,
            # whether or not the heartbeat file has appeared yet.
            return self._clock() - self._start > self.grace_s
        return self.progress_age_s(rank) > self.deadline_s

    def hang_culprits(self, stalled: Iterable[int]) -> list[int]:
        """Pick the rank(s) that CAUSED a stall from the ranks exhibiting one.

        One wedged rank stalls the whole world: every peer eventually blocks
        inside a collective waiting for it, so after the deadline ALL ranks
        look hung. Timing cannot break the tie (the cascade completes within
        milliseconds), but progress content can: the culprit froze *before*
        its step, while peers dispatched at least one step further (async
        dispatch keeps their host loop — and progress marks — running until
        a device fetch blocks). The culprit is therefore the stalled rank
        with the LOWEST last-reported progress ``step``; a rank that never
        reported a step (wedged in startup) is always a culprit. Ties mean
        the signal is ambiguous — every tied rank is treated as a culprit
        rather than guessing.
        """
        stalled = list(stalled)
        if not stalled:
            return []
        steps = {r: self._last_step.get(r, float("-inf")) for r in stalled}
        lowest = min(steps.values())
        return [r for r in stalled if steps[r] == lowest]

    def stragglers(self, active: Iterable[int]) -> list[int]:
        known = [v for v in self._interval_ema.values() if v > 0]
        if not known:
            return []
        threshold = self.straggler_factor * statistics.median(known)
        out = []
        for rank in active:
            if rank not in self._seen_progress:
                continue
            age = self.progress_age_s(rank)
            if threshold < age <= self.deadline_s:
                out.append(rank)
        return out


class ClusterSupervisor:
    """Shared supervisor bones: chaos spec + injector, registry ownership,
    heartbeat cadence, and the per-run JSONL metrics sink.

    Subclasses own the domain semantics (the pod re-forms a collective
    world; the fleet routes a request ledger through replica mailboxes) —
    the core owns everything that was duplicated between them. The
    ``log_name`` class attribute prefixes every supervisor log line.
    """

    log_name = "cluster"

    def __init__(
        self,
        root_dir: str | Path,
        *,
        chaos: str | None = None,
        heartbeat_deadline_s: float,
        heartbeat_interval_s: float,
        spawn_grace_s: float,
        poll_interval_s: float,
        registry: MetricsRegistry | None = None,
        env: Mapping[str, str] | None = None,
    ) -> None:
        self.dir = Path(root_dir)
        self.chaos_spec = chaos or os.environ.get("DMT_CHAOS") or ""
        self.heartbeat_deadline_s = heartbeat_deadline_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.spawn_grace_s = spawn_grace_s
        self.poll_interval_s = poll_interval_s
        self.extra_env = dict(env or {})
        self._own_registry = registry is None
        self.registry = registry or MetricsRegistry()
        #: set by :meth:`_open_journal`; ``None`` until a run starts.
        self.incarnation: int | None = None
        self.journal: SupervisorJournal | None = None

    def _log(self, msg: str) -> None:
        print(f"{self.log_name}: {msg}", flush=True)

    def _open_books(self, sink_name: str) -> Optional[ChaosInjector]:
        """Create the run directory + JSONL metrics sink, and the chaos
        injector when a spec is present. Call once at the top of ``run``."""
        self.dir.mkdir(parents=True, exist_ok=True)
        self.registry.add_sink(JsonlSink(self.dir / sink_name))
        if self.chaos_spec.strip():
            return ChaosInjector(
                FaultPlan.parse(self.chaos_spec), registry=self.registry
            )
        return None

    @staticmethod
    def _kill_orphan(pid: int) -> None:
        """SIGKILL a journaled orphan by pid — there is no Popen handle,
        the process belonged to a dead incarnation. Group first (workers
        are session leaders, pgid == pid), then the pid alone; init reaps
        whatever dies, not us."""
        if pid <= 0:
            return
        try:
            os.killpg(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def _open_journal(self) -> tuple[SupervisorJournal, list[dict]]:
        """Bump this run's incarnation, replay whatever a dead predecessor
        journaled (complete records only — a torn final line is dropped by
        the ``tail_jsonl`` discipline), and open the write-ahead journal
        for appending. Returns ``(journal, prior_records)``; the subclass
        decides what to do with the corpse's history (the fleet re-adopts
        orphans from it, the pod resumes attempt numbering)."""
        self.dir.mkdir(parents=True, exist_ok=True)
        prior = replay_journal(self.dir / JOURNAL_FILE)
        self.incarnation = next_incarnation(self.dir)
        self.journal = SupervisorJournal(
            self.dir, incarnation=self.incarnation
        )
        self.journal.record(
            "supervisor_start", pid=os.getpid(),
            prior_records=len(prior),
            prior_incarnations=sorted({r.get("inc") for r in prior
                                       if r.get("inc") is not None}),
        )
        return self.journal, prior

    def new_tracker(
        self,
        ranks: Iterable[int],
        *,
        grace_s: float | None = None,
        straggler_factor: float = 4.0,
    ) -> LivenessTracker:
        """A :class:`LivenessTracker` on this supervisor's cadence knobs.
        Trackers inherit this run's incarnation so heartbeats written
        under a dead control plane are rejected, not read as progress."""
        return LivenessTracker(
            ranks,
            deadline_s=self.heartbeat_deadline_s,
            grace_s=self.spawn_grace_s if grace_s is None else grace_s,
            straggler_factor=straggler_factor,
            incarnation=self.incarnation,
        )

    def _close_registry(self) -> None:
        if self._own_registry:
            self.registry.close()
