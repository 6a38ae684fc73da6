"""The Pallas flash kernel on a mesh of more than one device.

XLA's SPMD partitioner cannot split a Mosaic custom call: a ``pallas_call``
inside a plain ``jit`` over several TPU devices fails to lower with "Mosaic
kernels cannot be automatically partitioned. Please wrap the call in a
shard_map" (first met on four chips in PR 21 — the Pallas interpreter lowers
to plain HLO, which GSPMD partitions happily, so no CPU mesh could show it).
Attention is independent per (batch row, head), so the wrap needs no
collective: batch rows stay on their ``data`` shard, heads on the ``model``
shard tensor parallelism's column-sharded q/k/v projections already leave
them on, and every device runs the single-device kernel on its
``[B/dp, H/tp, S, D]`` block.
"""

from __future__ import annotations

import functools
from typing import Any

from jax.sharding import Mesh, PartitionSpec as P

from deeplearning_mpi_tpu.ops.pallas.flash_attention import flash_attention_bhsd
from deeplearning_mpi_tpu.runtime.compat import shard_map
from deeplearning_mpi_tpu.runtime.mesh import AXIS_DATA, AXIS_MODEL


def make_flash_attention_fn(mesh: Mesh) -> Any:
    """``--attention flash`` for ``mesh``: :func:`flash_attention_bhsd`
    itself on one device, else the same kernel under a ``shard_map`` over
    ``[B, H, S, D]`` with batch on ``data`` and heads on ``model`` (manual
    over every mesh axis, as Mosaic requires; the other axes see replicas).
    Same contract and ``.layout`` as :func:`flash_attention_bhsd`."""
    if mesh.size == 1:
        return flash_attention_bhsd
    spec = P(AXIS_DATA, AXIS_MODEL, None, None)
    dp, tp = mesh.shape[AXIS_DATA], mesh.shape[AXIS_MODEL]

    @functools.lru_cache(maxsize=4)
    def sharded(causal: bool, window: int | None):
        return shard_map(
            lambda q, k, v: flash_attention_bhsd(
                q, k, v, causal=causal, window=window
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )

    def attention_fn(q, k, v, *, causal: bool = True,
                     window: int | None = None):
        batch, heads = q.shape[:2]
        if batch % dp == 0 and heads % tp == 0:
            return sharded(causal, window)(q, k, v)
        if batch == 1:
            # model.init's batch-1 param-shaping forward: attention has no
            # params, so jit drops the call before it is lowered.
            return flash_attention_bhsd(q, k, v, causal=causal, window=window)
        raise ValueError(
            f"flash attention input [batch={batch}, heads={heads}] is not "
            f"divisible by the mesh (data={dp}, model={tp}); change the "
            "batch size or the mesh axes"
        )

    attention_fn.layout = flash_attention_bhsd.layout
    return attention_fn
