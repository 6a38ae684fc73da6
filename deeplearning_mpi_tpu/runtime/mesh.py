"""Device mesh construction and sharding helpers.

The reference's only parallelism is 1-D data parallelism (DDP wrap at
``pytorch/resnet/main.py:44-46``, ``pytorch/unet/train.py:68-70``; see
``SURVEY.md`` §2c). The TPU-native design goes through a named
``jax.sharding.Mesh`` from day one, with **five** named axes so that tensor,
pipeline, sequence/context, and expert parallelism are additive sharding
changes rather than rearchitectures. Unused axes have size 1 — they cost
nothing at compile time and keep every ``PartitionSpec`` in the codebase
stable as parallelism strategies are turned on.

Axis convention (ordered outermost → innermost; innermost axes get the
fastest ICI loops):

- ``data``   — batch sharding + gradient all-reduce (the reference's DDP).
- ``pipe``   — pipeline stages.
- ``expert`` — MoE expert sharding.
- ``seq``    — sequence/context parallelism (ring attention).
- ``model``  — tensor parallelism (megatron-style sharded matmuls).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"

#: All mesh axes, outermost first. DCN-friendly axes (data, pipe) come first so
#: that on multi-slice topologies the large-volume / latency-tolerant
#: collectives (gradient all-reduce, pipeline bubbles) map onto DCN while
#: latency-critical tensor/sequence collectives stay on intra-slice ICI.
MESH_AXES = (AXIS_DATA, AXIS_PIPE, AXIS_EXPERT, AXIS_SEQ, AXIS_MODEL)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Requested parallelism degrees. ``data=-1`` means "all remaining devices"."""

    data: int = -1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int, int, int]:
        fixed = self.pipe * self.expert * self.seq * self.model
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by "
                    f"pipe*expert*seq*model={fixed}"
                )
            data = n_devices // fixed
        total = data * fixed
        if total != n_devices:
            raise ValueError(
                f"mesh {data}x{self.pipe}x{self.expert}x{self.seq}x{self.model}"
                f" = {total} != device count {n_devices}"
            )
        return (data, self.pipe, self.expert, self.seq, self.model)


def order_devices_for_mesh(devices: list, shape: tuple[int, ...]) -> np.ndarray:
    """Arrange devices into the mesh array, multi-slice (DCN) aware.

    Single slice (or CPU/GPU, where ``slice_index`` doesn't exist): plain
    row-major reshape — device order from ``jax.devices()`` is already
    ICI-topology-sorted within a slice.

    Multi-slice TPU (devices carry distinct ``slice_index``): the slice
    boundaries must land inside the leading ``(data, pipe)`` block — the two
    DCN-friendly axes per the ``MESH_AXES`` contract (gradient all-reduce is
    large and latency-tolerant; pipeline ppermutes cross a boundary once per
    microbatch) — while ``expert``/``seq``/``model`` collectives
    (latency-critical, per-layer) stay on intra-slice ICI. Concretely the
    devices are laid out slice-major, which requires equal-size slices and
    each slice holding a whole number of ``expert*seq*model`` inner blocks.
    This is the placement ``jax.experimental.mesh_utils.
    create_hybrid_device_mesh`` produces with ``dcn_mesh_shape`` over
    (data, pipe), implemented directly so the grouping logic is
    unit-testable without multi-slice hardware.
    """
    groups: dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0) or 0, []).append(d)
    if len(groups) <= 1:
        return np.asarray(devices).reshape(shape)
    ordered = [groups[k] for k in sorted(groups)]
    per_slice = len(ordered[0])
    if any(len(g) != per_slice for g in ordered):
        raise ValueError(
            f"slices have unequal device counts: { {k: len(v) for k, v in groups.items()} }"
        )
    n_slices = len(ordered)
    inner = math.prod(shape[2:])  # expert * seq * model — ICI-only axes
    dcn_block = shape[0] * shape[1]  # data * pipe — may span slices
    if per_slice % inner or dcn_block % n_slices:
        raise ValueError(
            f"mesh {shape} cannot map onto {n_slices} slices of {per_slice} "
            f"devices: expert*seq*model ({inner}) must divide the per-slice "
            f"device count and data*pipe ({dcn_block}) must be a multiple of "
            "the slice count — only the data/pipe axes may cross DCN"
        )
    stacked = np.stack([np.asarray(g, dtype=object) for g in ordered])
    return stacked.reshape(shape)


def create_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: list[jax.Device] | None = None,
) -> Mesh:
    """Build the framework's canonical 5-axis mesh.

    With no arguments this is the DDP-parity configuration: every device on
    the ``data`` axis, all other axes size 1 — the TPU-native equivalent of
    the reference's world of N DDP ranks (``pytorch/resnet/main.py:44-46``).
    On multi-slice TPU topologies the device order is DCN-aware — see
    :func:`order_devices_for_mesh`.
    """
    spec = spec or MeshSpec()
    if devices is None:
        devices = jax.devices()
    shape = spec.resolve(len(devices))
    return Mesh(order_devices_for_mesh(devices, shape), MESH_AXES)


def occupied_devices(tree: Any) -> int:
    """How many devices the arrays of ``tree`` sit on. Not
    ``jax.device_count()``: work placed on one chip of a four-chip host
    occupies one, and a rate divided by four would be a quarter of the
    truth."""
    return len({
        d for leaf in jax.tree.leaves(tree) for d in leaf.sharding.device_set
    })


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes a global batch dimension is sharded over.

    Batch is sharded over every non-model axis that has size > 1 except
    ``seq`` (which shards the sequence dimension) — by default just
    ``data``. Folding ``expert`` in would be wrong (experts see the whole
    batch via all-to-all), so only ``data`` and ``pipe``-microbatching axes
    qualify; pipeline microbatching is handled by the pipeline schedule, so
    this returns ``('data',)``.
    """
    del mesh
    return (AXIS_DATA,)


def batch_sharding(mesh: Mesh, ndim: int = 4) -> NamedSharding:
    """Sharding for a batch tensor: leading dim over ``data``, rest replicated.

    The TPU-native replacement for ``DistributedSampler``'s rank-sharding of
    the dataset (``pytorch/resnet/main.py:94``, ``pytorch/unet/train.py:96``):
    instead of each rank holding a private batch, one *global* array is
    sharded over the ``data`` axis and XLA partitions the program.
    """
    return NamedSharding(mesh, P(data_axes(mesh), *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding — model parameters in pure DP (parity with
    DDP's replicate-everywhere model, ``pytorch/resnet/main.py:44-46``)."""
    return NamedSharding(mesh, P())


def local_batch_size(global_batch_size: int, mesh: Mesh) -> int:
    """Number of examples of a global batch this process must supply.

    The reference's ``--batch_size`` is *per process* (``torchrun`` spawns one
    process per GPU; ``pytorch/resnet/main.py:164``). This framework uses
    *global* batch sizes everywhere and derives the per-host share from the
    batch sharding's actual addressable shards — correct even when
    model/seq axes span processes (where a flat ``global // process_count``
    would be wrong: a process whose devices replicate the batch along
    ``model`` still only needs its distinct ``data``-axis rows).
    """
    n_data = math.prod(mesh.shape[a] for a in data_axes(mesh))
    if global_batch_size % n_data != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by data-parallel "
            f"degree {n_data}"
        )
    sharding = batch_sharding(mesh, ndim=1)
    pid = jax.process_index()
    local_rows: set[tuple[int, int]] = set()
    for dev, index in sharding.devices_indices_map((global_batch_size,)).items():
        if dev.process_index == pid:
            sl = index[0]
            local_rows.add((sl.start or 0, sl.stop or global_batch_size))
    return sum(stop - start for start, stop in local_rows)
