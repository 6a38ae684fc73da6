"""One spelling of the JAX APIs this codebase routes through a seam.

Every call site uses the names below rather than reaching into jax
directly, so an upstream rename lands in one file:

- :func:`shard_map` — ``jax.shard_map`` with ``check_vma`` / ``axis_names``
  passed only when given (``None`` takes the library default).
- :func:`axis_size` / :func:`pcast` — ``lax.axis_size`` and a tree-mapped
  ``lax.pcast``.
- :func:`tpu_compiler_params` — the Pallas TPU compiler-params dataclass.
- :func:`buffer_donation_supported` — the compile-cache donation veto.
"""

from __future__ import annotations

from typing import Any

import jax
from jax import lax

__all__ = [
    "axis_size",
    "buffer_donation_supported",
    "pcast",
    "shard_map",
    "tpu_compiler_params",
]


def buffer_donation_supported() -> bool:
    """Whether ``jit`` buffer donation is safe on this backend configuration.

    Back-compat shim over ``compiler.cache.donation_safe`` — the hazard is
    a persistent-compile-cache property (donated inputs + a cache-
    DESERIALIZED executable corrupt the heap on XLA:CPU), so the policy
    lives with the cache's owner, ``deeplearning_mpi_tpu/compiler/cache.py``,
    which documents the full failure mode and carries the regression test
    (``tests/test_compiler.py``). Existing call sites (trainer and serving
    jit construction) keep this name.
    """
    from deeplearning_mpi_tpu.compiler.cache import donation_safe

    return donation_safe()


def shard_map(
    f,
    *,
    mesh,
    in_specs,
    out_specs,
    check_vma: bool | None = None,
    axis_names: Any = None,
):
    """``jax.shard_map``. ``axis_names`` (when given) is the set of mesh
    axes the function is MANUAL over; ``check_vma=None`` takes the library
    default."""
    kw: dict[str, Any] = {}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw
    )


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis, inside shard_map/pmap bodies."""
    return lax.axis_size(axis_name)


def pcast(x, axis_names, *, to: str = "varying"):
    """``lax.pcast`` over every leaf of ``x``."""
    return jax.tree.map(lambda a: lax.pcast(a, tuple(axis_names), to=to), x)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` (``dimension_semantics`` et al.)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
