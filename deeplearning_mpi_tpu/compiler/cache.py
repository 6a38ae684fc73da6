"""Persistent-compile-cache management: keying, eviction, quarantine, policy.

JAX's persistent compilation cache turns the second run of any program into
a deserialization (~0.5 ms) instead of an XLA compile (seconds to minutes
at scale), but the cache directory itself has no owner: nothing bounds its
size, nothing notices a corrupt entry until XLA chokes on it, and nothing
counts how often it actually saves a compile. This module is that owner:

- :func:`donation_safe` — the single home of the buffer-donation veto
  policy (PR 3 discovered it; ``runtime/compat.buffer_donation_supported``
  now delegates here);
- :class:`CompileCache` — entry listing/keying, hit/miss accounting via
  directory snapshots (``compile_cache_hit_total`` / ``_miss_total`` /
  ``compile_seconds``), a digest manifest over the entries (reusing
  ``resilience/integrity.py``'s sha256 machinery), corrupt-entry
  quarantine, and size-bounded LRU eviction.

Cache layout (jaxlib 0.9.0, re-checked against ``.jax_cache/``): each
executable is one ``jit_<name>-<hash>-cache`` file plus a ``-atime`` sibling
the runtime touches on every cache READ — which is exactly the LRU signal
eviction wants, and exactly why the manifest covers only ``*-cache``
files (the atime siblings legitimately change between verifications).
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Any

import jax

from deeplearning_mpi_tpu.resilience.integrity import (
    atomic_write_json,
    dir_digests,
)

__all__ = [
    "CACHE_SUFFIX",
    "CacheEntry",
    "CompileCache",
    "DEFAULT_CACHE_DIR",
    "cache_dir",
    "configure",
    "donation_safe",
    "enable",
]

#: Suffix of one serialized executable in the cache directory.
CACHE_SUFFIX = "-cache"
#: Suffix of the access-time sibling jax touches on cache reads.
ATIME_SUFFIX = "-atime"
#: Digest manifest filename (inside the cache dir; filtered out of entries).
MANIFEST_NAME = "cache-manifest.json"
#: Subdirectory corrupt entries are moved to (never deleted: evidence).
QUARANTINE_DIR = "quarantine"


#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: directory under the checkout (gitignored). The directory is part of the
#: cache key, so it is never derived from ``tempfile``, a pid or the time —
#: a cache that moves between runs never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> Path:
    """Place the persistent compilation cache; every entry point calls this
    before its first backend use (``runtime.bootstrap.select_platform``).

    ``JAX_COMPILATION_CACHE_DIR`` set: jax has already read it, so nothing
    is set in code and the operator's placement stands. Unset:
    :data:`DEFAULT_CACHE_DIR`. Every program is cached however quickly it
    compiled (a warm start should compile nothing) unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env)
    else:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", str(path))
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_dir() -> Path | None:
    """The configured persistent-cache directory, or None when disabled."""
    d = jax.config.jax_compilation_cache_dir
    return Path(d) if d else None


def enable(path: str | Path, *, min_compile_time_secs: float = 0.0) -> Path:
    """Point jax's persistent compilation cache at ``path`` (created if
    missing). ``min_compile_time_secs=0`` caches everything — warmup wants
    even trivially-cheap programs persisted so a warm start never compiles."""
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    _reset_backend_cache()
    return path


def _reset_backend_cache() -> None:
    """Drop jax's pinned cache object so a config change takes effect.

    The runtime initializes its persistent-cache handle lazily at the
    first compile and then keeps it — updating
    ``jax_compilation_cache_dir`` after that point is silently ignored
    until the handle is reset (private API, so failures are swallowed:
    worst case the redirect only applies to a fresh process)."""
    try:
        from jax._src.compilation_cache import reset_cache

        reset_cache()
    except Exception:
        pass


def donation_safe(
    backend: str | None = None, cache_enabled: bool | None = None
) -> bool:
    """Whether ``jit`` buffer donation is safe on this backend configuration
    — the compile-cache policy that vetoes it, owned here because the hazard
    IS the cache.

    False on XLA:CPU when the persistent compilation cache is enabled:
    executing a cache-DESERIALIZED executable with donated inputs after an
    in-process orbax/tensorstore checkpoint restore corrupts the native
    heap — segfault or ``malloc()`` abort inside
    ``ThunkExecutor::ProcessOutEdges`` (seen on jaxlib 0.4.36; fresh-compiled
    executables and non-donating deserialized ones were both immune. A
    restore-then-donated-step sketch ran clean 5/5 on jaxlib 0.9.0, but the
    original script is not in the tree, so the veto stays as safety code
    until a test retires it). That sequence is exactly crash
    auto-resume — train, crash, restore, retrain — under a warm compile
    cache, the configuration the test suite runs. Donation is a memory
    optimization, never semantics, so the guard costs only transient
    buffers on the backend where model state is smallest; TPU/GPU and
    cache-less CPU runs keep donating.

    ``backend``/``cache_enabled`` default to the live configuration; tests
    pass them explicitly to pin the policy matrix without reconfiguring jax.
    """
    if backend is None:
        backend = jax.default_backend()
    if cache_enabled is None:
        cache_enabled = bool(jax.config.jax_compilation_cache_dir)
    return not (backend == "cpu" and cache_enabled)


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One serialized executable in the cache directory."""

    name: str
    path: Path
    size_bytes: int
    #: LRU signal: the ``-atime`` sibling's mtime (jax touches it on every
    #: cache read), falling back to the entry's own mtime.
    last_used: float


class CompileCache:
    """Management handle over one persistent-cache directory.

    ``path=None`` binds to whatever directory jax is configured with *at
    each call* (so ``enable()`` mid-process is picked up); when no cache is
    configured every operation degrades to a no-op/empty result rather than
    raising — callers never need to branch on cache availability.

    ``registry`` (a ``telemetry.MetricsRegistry``) receives the
    ``compile_cache_hit_total`` / ``compile_cache_miss_total`` /
    ``compile_cache_evicted_total`` / ``compile_cache_quarantined_total``
    counters and the ``compile_seconds`` histogram.
    """

    def __init__(self, path: str | Path | None = None, registry: Any = None):
        self._path = Path(path) if path else None
        self.registry = registry
        if registry is not None:
            for name in (
                "compile_cache_hit_total", "compile_cache_miss_total",
                "compile_cache_evicted_total",
                "compile_cache_quarantined_total",
            ):
                registry.counter(name)
            registry.histogram("compile_seconds")

    @property
    def path(self) -> Path | None:
        return self._path if self._path is not None else cache_dir()

    @property
    def enabled(self) -> bool:
        p = self.path
        return p is not None and p.is_dir()

    # -- entry listing -------------------------------------------------------
    def entries(self) -> list[CacheEntry]:
        """Every serialized executable, newest-used last (LRU order)."""
        if not self.enabled:
            return []
        out = []
        for f in self.path.iterdir():
            if not (f.is_file() and f.name.endswith(CACHE_SUFFIX)):
                continue
            atime = f.with_name(
                f.name[: -len(CACHE_SUFFIX)] + ATIME_SUFFIX
            )
            try:
                last = (atime if atime.exists() else f).stat().st_mtime
                size = f.stat().st_size
            except OSError:
                continue  # racing eviction/quarantine from another process
            out.append(CacheEntry(f.name, f, size, last))
        return sorted(out, key=lambda e: (e.last_used, e.name))

    def size_bytes(self) -> int:
        return sum(e.size_bytes for e in self.entries())

    def snapshot(self) -> frozenset[str]:
        """Entry names right now — diff two snapshots around a compile to
        tell a persistent-cache hit (no new file) from a miss (new file).
        Names alone, no stat per entry: two of these bracket every warmed
        program, and :meth:`entries` costs 0.16 s over 230 entries on the
        chip's host."""
        if not self.enabled:
            return frozenset()
        return frozenset(
            n for n in os.listdir(self.path) if n.endswith(CACHE_SUFFIX)
        )

    # -- hit/miss accounting -------------------------------------------------
    def observe_compile(
        self, name: str, seconds: float, before: frozenset[str] | None
    ) -> bool | None:
        """Classify one just-finished compile against a pre-compile
        :meth:`snapshot` and record the telemetry. Returns True (cache hit —
        the executable deserialized), False (miss — a new entry appeared),
        or None (cache disabled: no hit/miss semantics, time still
        recorded)."""
        hit: bool | None = None
        if before is not None and self.enabled:
            hit = not (self.snapshot() - before)
        if self.registry is not None:
            self.registry.histogram("compile_seconds").observe(seconds)
            if hit is True:
                self.registry.counter("compile_cache_hit_total").inc()
            elif hit is False:
                self.registry.counter("compile_cache_miss_total").inc()
        return hit

    # -- integrity: manifest, verify, quarantine -----------------------------
    def _entry_digests(self) -> dict[str, str]:
        # dir_digests walks recursively; keep only top-level *-cache files —
        # atime siblings change on every read and the quarantine/ subtree is
        # the verdict, not the evidence.
        return {
            k: v for k, v in dir_digests(self.path).items()
            if k.endswith(CACHE_SUFFIX) and os.sep not in k
        }

    def write_manifest(self) -> dict[str, str]:
        """Digest every entry (sha256, ``resilience/integrity.py``) into
        ``cache-manifest.json`` beside them; returns the digests."""
        if not self.enabled:
            return {}
        digests = self._entry_digests()
        atomic_write_json(self.path / MANIFEST_NAME, {"digests": digests})
        return digests

    def verify(self, *, quarantine: bool = True) -> list[str]:
        """Compare entries against the manifest; returns the corrupt names.

        ``quarantine`` moves each mismatched entry (and its atime sibling)
        into ``quarantine/`` instead of leaving it for XLA to choke on —
        the next lookup of that key recompiles and re-caches cleanly.
        Entries without a manifest record are new since the last
        :meth:`write_manifest` and pass (same accept-unverified stance as
        checkpoint manifests)."""
        if not self.enabled:
            return []
        import json

        try:
            manifest = json.loads((self.path / MANIFEST_NAME).read_text())
            recorded = dict(manifest["digests"])
        except (OSError, ValueError, KeyError, TypeError):
            return []
        bad = [
            name for name, digest in self._entry_digests().items()
            if name in recorded and recorded[name] != digest
        ]
        if quarantine and bad:
            qdir = self.path / QUARANTINE_DIR
            qdir.mkdir(exist_ok=True)
            for name in bad:
                entry = self.path / name
                os.replace(entry, qdir / name)
                atime = self.path / (
                    name[: -len(CACHE_SUFFIX)] + ATIME_SUFFIX
                )
                if atime.exists():
                    os.replace(atime, qdir / atime.name)
            if self.registry is not None:
                self.registry.counter(
                    "compile_cache_quarantined_total"
                ).inc(len(bad))
        return bad

    # -- size-bounded eviction -----------------------------------------------
    def evict(self, max_bytes: int) -> list[CacheEntry]:
        """Delete least-recently-used entries until the cache fits in
        ``max_bytes``; returns what was evicted. The ``-atime`` sibling is
        the recency signal (jax touches it on every cache read), so an
        entry that keeps getting hits survives entries that were compiled
        later but never reused."""
        if not self.enabled:
            return []
        entries = self.entries()
        total = sum(e.size_bytes for e in entries)
        evicted: list[CacheEntry] = []
        for e in entries:  # oldest-used first
            if total <= max_bytes:
                break
            try:
                e.path.unlink()
                atime = e.path.with_name(
                    e.name[: -len(CACHE_SUFFIX)] + ATIME_SUFFIX
                )
                if atime.exists():
                    atime.unlink()
            except OSError:
                continue
            total -= e.size_bytes
            evicted.append(e)
        if self.registry is not None and evicted:
            self.registry.counter("compile_cache_evicted_total").inc(
                len(evicted)
            )
        return evicted

    def stats(self) -> dict[str, Any]:
        entries = self.entries()
        return {
            "path": str(self.path) if self.path else None,
            "enabled": self.enabled,
            "entries": len(entries),
            "size_bytes": sum(e.size_bytes for e in entries),
        }
