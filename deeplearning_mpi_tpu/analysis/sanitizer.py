"""Opt-in runtime sanitizer (``DMT_SANITIZE=1``): the lint rules' contracts
enforced dynamically, on live state the AST cannot see.

Three tripwires, each the runtime half of a static rule:

- **KV-block poisoning** (``sanitize_kv_double_free_total`` /
  ``sanitize_kv_use_after_free_total``): :class:`KVPoolSanitizer` rides
  inside :class:`~deeplearning_mpi_tpu.serving.kv_pool.PagedKVPool` and
  marks every freed block *poisoned* until it is re-allocated. A second
  free of a poisoned block is a double-free; a data/scale write recorded
  against a poisoned block is a use-after-free. Both fail loud with
  :class:`SanitizerError` instead of the generic accounting ValueError, so
  a drill (and a production run) can tell "caller freed twice" from
  "caller never owned it". The prefix-cache refcount layer adds two more
  classes on the same pool: a refcount decremented below zero
  (``sanitize_kv_refcount_underflow_total`` — the books say nobody owns a
  block that is still in the used set) and a data/scale write recorded
  against a block whose refcount is > 1
  (``sanitize_kv_cow_violation_total`` — a writer skipped the
  copy-on-write step and is mutating pages another sharer still reads).
- **Retrace tripwire** (``sanitize_retrace_trips_total``): after a serving
  engine's :meth:`warmup` completes, the zero-compile contract is armed —
  any ``serve_compile_total`` tick raises.
- **Donation canary** (``sanitize_donation_canary_trips_total``):
  :func:`donation_canary` hashes a state leaf before checkpoint save and
  re-verifies it after the save barrier — the PR 3 aliasing bug (async
  serializer still holding views of buffers the donated next step reuses
  in place) flips the canary where it silently corrupted checkpoints.

The sanitizer is costless when off: every hook is gated on
:func:`enabled`, which reads ``DMT_SANITIZE`` once per call site at object
construction time (pools/engines built before the env flag flips stay
unsanitized). Trips are counted module-globally (:func:`trip_counts`) and
mirrored into an attached :class:`MetricsRegistry` under ``sanitize_*``
counter names so ``tools/metrics_report.py`` can render them.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Iterable

__all__ = [
    "KVPoolSanitizer",
    "SanitizerError",
    "attach_registry",
    "check_compile_tick",
    "donation_canary",
    "enabled",
    "reset_trips",
    "trip",
    "trip_counts",
]

KV_DOUBLE_FREE = "sanitize_kv_double_free_total"
KV_USE_AFTER_FREE = "sanitize_kv_use_after_free_total"
KV_REFCOUNT_UNDERFLOW = "sanitize_kv_refcount_underflow_total"
KV_COW_VIOLATION = "sanitize_kv_cow_violation_total"
RETRACE_TRIPS = "sanitize_retrace_trips_total"
DONATION_TRIPS = "sanitize_donation_canary_trips_total"


class SanitizerError(RuntimeError):
    """A sanitized contract was violated. Always fatal by design — the
    sanitizer exists to fail loud where production would corrupt quietly."""


_trips: dict[str, int] = {}
_registry: Any = None


def enabled() -> bool:
    """True when ``DMT_SANITIZE`` is set to anything but ''/'0'."""
    return os.environ.get("DMT_SANITIZE", "") not in ("", "0")


def attach_registry(registry: Any) -> None:
    """Mirror trip counters into a MetricsRegistry (``sanitize_*`` names)."""
    global _registry
    _registry = registry
    if registry is not None:
        for name in (KV_DOUBLE_FREE, KV_USE_AFTER_FREE,
                     KV_REFCOUNT_UNDERFLOW, KV_COW_VIOLATION,
                     RETRACE_TRIPS, DONATION_TRIPS):
            registry.counter(name)


def trip(name: str, message: str) -> None:
    """Count a trip and raise. The count lands BEFORE the raise so a
    caller that catches (the drill) still sees it in :func:`trip_counts`
    and in the attached registry's run summary. Any live flight recorders
    dump here too — same reasoning: the evidence must land before the
    exception starts unwinding whoever corrupted the state."""
    _trips[name] = _trips.get(name, 0) + 1
    if _registry is not None:
        try:
            _registry.counter(name).inc()
        except Exception:
            pass
    try:
        from deeplearning_mpi_tpu.telemetry import spans as _spans

        _spans.dump_all(f"sanitizer-{name}")
    except Exception:
        pass  # a failed dump must never mask the trip itself
    raise SanitizerError(f"[{name}] {message}")


def trip_counts() -> dict[str, int]:
    return dict(_trips)


def reset_trips() -> None:
    _trips.clear()


# -- retrace tripwire --------------------------------------------------------

def check_compile_tick(*, post_warmup: bool, what: str = "serving program") -> None:
    """Called where ``serve_compile_total`` ticks. A tick after warmup is a
    retrace — the zero-compile contract every serving drill asserts."""
    if not post_warmup or not enabled():
        return
    trip(
        RETRACE_TRIPS,
        f"{what} compiled AFTER warmup: the zero-retrace contract is "
        "violated — a shape/dtype/static-arg reached the jit boundary "
        "that warmup never traced",
    )


# -- KV pool poisoning -------------------------------------------------------

class KVPoolSanitizer:
    """Freed-block poison set for one :class:`PagedKVPool`.

    Poisoning is accounting-level: the pool is host-side bookkeeping (the
    device pages are owned by the engine), so the poison marker lives on
    the block id. That is exactly where the bug class lives too — every
    past KV incident was a block-table entry pointing at a block the free
    list had already handed to someone else."""

    def __init__(self) -> None:
        self.poisoned: set[int] = set()

    def on_alloc(self, blocks: Iterable[int]) -> None:
        self.poisoned.difference_update(blocks)

    def check_free(self, blocks: Iterable[int], used: set[int]) -> None:
        for b in blocks:
            if b in self.poisoned and b not in used:
                trip(
                    KV_DOUBLE_FREE,
                    f"double free of KV block {b}: it was already freed and "
                    "is poisoned — a second owner would have corrupted its "
                    "pages",
                )

    def on_free(self, blocks: Iterable[int]) -> None:
        self.poisoned.update(blocks)

    def check_touch(self, blocks: Iterable[int], used: set[int], kind: str) -> None:
        for b in blocks:
            if b in self.poisoned and b not in used:
                trip(
                    KV_USE_AFTER_FREE,
                    f"{kind} write recorded against freed KV block {b}: a "
                    "stale block-table entry is scattering into poisoned "
                    "pages (use-after-free)",
                )


# -- donation canary ---------------------------------------------------------

class _DonationCanary:
    def __init__(self, digest: str, leaf_path: str) -> None:
        self._digest = digest
        self._leaf_path = leaf_path

    def verify(self, state: Any) -> None:
        digest, _ = _canary_digest(state)
        if digest != self._digest:
            trip(
                DONATION_TRIPS,
                f"state leaf {self._leaf_path} changed across checkpoint "
                "save: an async serializer or donated executable aliased "
                "the live buffers (PR 3 bug class) — the saved bytes are "
                "not the state that was passed in",
            )


def _canary_digest(state: Any) -> tuple[str, str]:
    import jax
    import numpy as np

    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    arrays = [(p, x) for p, x in leaves if hasattr(x, "dtype")]
    if not arrays:
        return "", ""
    # Smallest leaf: the canary must be cheap enough to run on every save.
    path, leaf = min(arrays, key=lambda px: getattr(px[1], "size", 0))
    host = np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
    h = hashlib.sha256()
    h.update(str(host.dtype).encode())
    h.update(str(host.shape).encode())
    h.update(host.tobytes())
    return h.hexdigest(), jax.tree_util.keystr(path)


def donation_canary(state: Any) -> _DonationCanary:
    """Hash one (small) state leaf; ``verify`` after the save barrier."""
    digest, path = _canary_digest(state)
    return _DonationCanary(digest, path)
