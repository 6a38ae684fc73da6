"""Trace annotations for the parallel hot paths and the serving engine's step.

``Profiler`` traces (``utils.profiling``) were unreadable before this
module: every ring rotation, all-to-all, pipeline step, and Pallas kernel
launch appeared as anonymous XLA fusions. :func:`annotate` stamps both
layers a trace has:

- ``jax.named_scope`` — trace-time: the scope name lands in the HLO op
  metadata of every op created inside it, so the device timeline in
  TensorBoard/Perfetto groups ops under ``ring_attention/rotation``-style
  names instead of ``fusion.1234``.
- ``jax.profiler.TraceAnnotation`` — host-side runtime: dispatch/placement
  work executed while the context is open shows on the Python track.

Which one where: :func:`annotate` in code JAX traces (it runs once per
compilation, and only there does the named scope mean anything);
:func:`span` in host code that runs every step — it is the
``TraceAnnotation`` alone, an order of magnitude cheaper, and carries
labels (``serving/engine.py``'s ``serve/`` spans).

Annotation is pure metadata — it must never change computed values. The
``enabled`` switch exists so tests can prove that (run a step annotated and
un-annotated, assert bit-identical outputs) and so a paranoid run can strip
annotations wholesale; the compute inside the context is identical either
way.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator

import jax

_ENABLED = True


def set_enabled(flag: bool) -> bool:
    """Globally enable/disable annotation emission; returns the old value.

    Exists for the no-op proof in tests and for excluding annotation
    overhead from microbenchmarks — NOT a perf knob (named_scope costs
    nothing at runtime; TraceAnnotation costs nothing outside an active
    profiler session).
    """
    global _ENABLED
    old = _ENABLED
    _ENABLED = bool(flag)
    return old


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Context manager: HLO named scope + host trace annotation for ``name``."""
    if not _ENABLED:
        yield
        return
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


class _Off:
    """What :func:`span` hands out while annotation is disabled."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set_metadata(self, **labels: Any) -> None:
        pass


_OFF = _Off()


def span(name: str, **labels: Any) -> Any:
    """Host-only span for per-step host code: the bare
    ``jax.profiler.TraceAnnotation`` — no ``named_scope`` (nothing is being
    traced by JAX), no generator frame. ``labels`` come back as the event's
    ``stats`` from ``jax.profiler.ProfileData``; labels known only at the end
    of the span go through ``set_metadata(**labels)`` on the entered object.
    With no profiler session it costs well under a microsecond."""
    if not _ENABLED:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **labels)
