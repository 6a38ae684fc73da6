"""A serving program's hand-off to the device and its result's way back, in
host spans that tell what the chip waits for between two programs.

Every call site that hands a program to the device (``serving/engine.py``:
the decode step, the prefill chunk, the verify step, the copy-on-write copy;
``serving/speculative.py``: the draft's programs) writes, inside its own
``serve/*_launch`` (or ``serve/cow``) span and in this order:

- ``launch/prep`` — the host builds the step's numpy inputs (opened at the
  call site; a copy-on-write copy builds nothing and has none);
- ``launch/h2d`` (:func:`h2d`) — every host-to-device transfer of them;
  label ``bytes``, what was handed over;
- ``launch/dispatch`` (:func:`dispatch`) — the call into the program until it
  returns; labels ``program`` (the executable's registered name, e.g.
  ``serve_decode_step@8x257``) and ``fallback`` (1 where ``WarmProgram``'s
  jit net ran it).

What follows the dispatch stays the launch span's own time. Every fetch
(``serve/token_fetch``, ``serve/first_token_fetch``, ``serve/verify_fetch``,
and the draft's inside ``serve/draft_launch``) is :func:`fetch`:
``fetch/ready`` (the host starts the copies back and waits for the program
and its completion signal), then ``fetch/d2h`` (the copy of the ready arrays back; label ``bytes``).

The prefixes are not ``serve/`` on purpose: ``benchmark/readers/serve_spans.py``
holds every ``serve/`` event inside a step to be a direct child that no
other overlaps; ``benchmark/readers/serve_idle.py`` reads the nesting.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_mpi_tpu.telemetry.trace import span


def _put(x: Any) -> jax.Array:
    return jnp.int32(x) if isinstance(x, (int, np.integer)) else jnp.asarray(x)


def h2d(*host: Any) -> tuple[Any, ...]:
    """``host`` on the device in one ``launch/h2d`` span, in order:
    ``jnp.asarray`` of each array, ``jnp.int32`` of each Python int, and a
    tuple (the two groups' block tables of a model with full and window
    layers) item by item into a tuple."""
    with span("launch/h2d") as sp:
        out = tuple(
            tuple(map(_put, x)) if isinstance(x, tuple) else _put(x)
            for x in host
        )
        sp.set_metadata(bytes=sum(  # an array already on the device moves nothing
            x.nbytes if isinstance(x, np.ndarray) else 4 * isinstance(x, (int, np.integer))
            for x in jax.tree.leaves(host)
        ))
    return out


def dispatch(fn: Callable[..., Any], *args: Any) -> Any:
    """``fn(*args)`` in one ``launch/dispatch`` span. ``fn`` is a
    ``compiler.aot.WarmProgram`` once the engine is warmed, else its jit."""
    with span("launch/dispatch") as sp:
        out = fn(*args)
        net = getattr(fn, "fallback", None)  # a WarmProgram's jit net
        if net is None:
            program, fell = f"jit_{fn.__name__}", 0
        elif fn.last is None:
            program, fell = f"jit_{net.__name__}", 1
        else:
            program, fell = fn.last, 0
        sp.set_metadata(program=program, fallback=fell)
    return out


def fetch(tree: Any) -> Any:  # dmt-lint: hot-loop
    """``jax.device_get(tree)``, waited for first, so that a trace tells the
    wait for the program (``fetch/ready``) from the copy back
    (``fetch/d2h``, label ``bytes``). ``fetch/ready`` starts the copies
    before it waits, as ``jax.device_get`` starts them: each then runs as
    soon as its program ends, where a copy asked for after the wait made
    ``fetch/d2h`` 0.09 ms a step longer on the v5e (`lm-serve-long`)."""
    with span("fetch/ready"):
        for leaf in jax.tree.leaves(tree):
            leaf.copy_to_host_async()
        jax.block_until_ready(tree)  # dmt-lint: disable=DMT003 — every serving fetch's one wait, the audited syncs of engine.py and speculative.py
    with span("fetch/d2h") as sp:
        out = jax.device_get(tree)  # dmt-lint: disable=DMT003 — the copy back of arrays fetch/ready has waited for
        sp.set_metadata(bytes=sum(x.nbytes for x in jax.tree.leaves(out)))
    return out
