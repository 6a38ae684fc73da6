"""Process bootstrap and topology discovery.

TPU-native replacement for the reference's launcher/rendezvous stack: torchrun
populates ``LOCAL_RANK``/``RANK``/``WORLD_SIZE``/``MASTER_ADDR``/``MASTER_PORT``
env vars which every entrypoint ingests before calling
``dist.init_process_group(backend)`` (reference:
``pytorch/hello_world/hello_world.py:7-13,34``,
``pytorch/resnet/main.py:18-20,148``, ``pytorch/unet/train.py:21-23,255``;
launched by ``pytorch/unet/run.sh:100-112``).

The TPU model differs in one fundamental way: one process per **host**, not one
per accelerator. Chips local to a host are addressed via
``jax.local_devices()``; cross-host communication rides ICI within a slice and
DCN across slices, owned entirely by the XLA runtime — there is no user-level
NCCL analog to manage. ``init()`` wraps ``jax.distributed.initialize`` and
accepts the same contract either from flags or from env vars:

=====================  =============================  =======================
reference (torchrun)    this framework (env var)       this framework (flag)
=====================  =============================  =======================
MASTER_ADDR:PORT        ``COORDINATOR_ADDRESS``        ``coordinator_address``
WORLD_SIZE              ``NUM_PROCESSES``              ``num_processes``
RANK                    ``PROCESS_ID``                 ``process_id``
backend nccl/gloo       ``JAX_PLATFORMS`` tpu/cpu      ``platform``
=====================  =============================  =======================

On an actual TPU pod slice all three topology values are discoverable from TPU
metadata, so ``init()`` with no arguments does the right thing both on a
single host and on a pod.
"""

from __future__ import annotations

import dataclasses
import os
import platform as _platform
import socket
from typing import Any

import jax

_initialized_distributed = False


def _distributed_active() -> bool:
    """Whether a live distributed client exists RIGHT NOW, asked of jax
    itself rather than our module flag: a caller may tear the runtime down
    with ``jax.distributed.shutdown()`` directly (elastic re-rendezvous does
    exactly this), leaving the flag stale — and a stale ``True`` would make
    the next :func:`init` silently skip the re-initialize, training N
    independent models. Falls back to the flag if jax's internals move."""
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception:  # noqa: BLE001 — private API; degrade to our own flag
        return _initialized_distributed


def _looks_like_tpu_pod() -> bool:
    """Detect a multi-host TPU slice from the TPU runtime's own env vars.

    On a pod slice every host gets ``TPU_WORKER_HOSTNAMES`` (comma-separated)
    and ``TPU_WORKER_ID`` from the TPU VM runtime; a single-host TPU VM either
    lacks them or lists one worker. This keeps no-arg :func:`init` correct on
    pods (where skipping ``jax.distributed.initialize`` would silently train N
    independent models) without paying the rendezvous cost on single hosts.
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1


def select_platform(platform: str | None = None) -> None:
    """Pre-backend set-up shared by every entry point: force ``platform``
    when given (``"tpu"`` makes a TPU that fails to initialise an error
    instead of a quiet CPU run) and place the compile cache
    (``compiler.cache.configure``). Must run before the first backend use."""
    from deeplearning_mpi_tpu.compiler import cache

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    cache.configure()


def set_virtual_cpu_devices(n: int) -> None:
    """Force ``n`` fake CPU devices — the hardware-free multi-device path.

    The moral equivalent of the reference running N Gloo processes on one
    machine (``pytorch/hello_world/hello_world.py:44``; SURVEY.md §4). Must be
    called before the first JAX backend use. Replaces (not appends to) any
    existing ``xla_force_host_platform_device_count`` in ``XLA_FLAGS``.
    """
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    select_platform("cpu")


@dataclasses.dataclass(frozen=True)
class Topology:
    """Snapshot of the distributed topology after :func:`init`.

    The moral equivalent of the reference's post-``init_process_group`` state
    (rank/world_size globals, ``pytorch/resnet/main.py:18-20``) plus the device
    inventory the reference obtains from ``torch.cuda`` calls
    (``pytorch/unet/train.py:28-32``).
    """

    process_id: int
    num_processes: int
    local_device_count: int
    global_device_count: int
    platform: str
    coordinator_address: str | None

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    platform: str | None = None,
) -> Topology:
    """Initialize the (possibly multi-host) JAX runtime and return topology.

    Single-process (the common single-host TPU VM case) needs no rendezvous at
    all — unlike the reference, where even one node must run torchrun to spawn
    one process per GPU (``pytorch/hello_world/run.sh:14-19``). Multi-host runs
    pass coordinator/num_processes/process_id via flags or env vars.

    ``platform`` forces a JAX platform ("tpu" or "cpu") — the analog of the
    reference's nccl/gloo backend switch (``pytorch/hello_world/hello_world.py:44``):
    the same program runs unchanged on CPU devices for hardware-free testing.
    """
    global _initialized_distributed

    select_platform(platform)

    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])

    multi_process = (
        coordinator_address is not None
        or (num_processes is not None and num_processes > 1)
        or _looks_like_tpu_pod()
    )
    if multi_process and not _distributed_active():
        # Safely re-enterable: after a shutdown (ours or a direct
        # jax.distributed.shutdown()), _distributed_active() is False and a
        # new rendezvous — possibly a different coordinator/world size, the
        # elastic re-form path — proceeds from scratch.
        _initialized_distributed = False
        plats = (
            platform
            or os.environ.get("JAX_PLATFORMS")
            or str(jax.config.read("jax_platforms") or "")
        )
        if "cpu" in plats:
            # Cross-process CPU collectives need the gloo transport (the
            # default CPU backend has none) — the reference's gloo backend
            # switch, applied automatically so pod workers launched from a
            # plain training CLI just work.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # With all-None args on a TPU pod, jax auto-discovers topology from
        # TPU metadata — the no-flag path for real slices.
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized_distributed = True

    return Topology(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
        platform=jax.devices()[0].platform,
        coordinator_address=coordinator_address,
    )


def shutdown() -> None:
    """Tear down the distributed runtime.

    Parity with ``dist.destroy_process_group()`` in the reference's
    ``finally`` blocks (``pytorch/hello_world/hello_world.py:37-39``,
    ``pytorch/resnet/main.py:149-153``, ``pytorch/unet/train.py:257-276``).
    A no-op in single-process mode, idempotent always: a double shutdown
    (or one following a direct ``jax.distributed.shutdown()``) must not
    raise, and the flag ALWAYS resets so a later :func:`init` can
    re-rendezvous — the elastic re-form path depends on init→shutdown→init
    round-tripping cleanly.
    """
    global _initialized_distributed
    was_distributed = _initialized_distributed or _distributed_active()
    try:
        if was_distributed:
            jax.distributed.shutdown()
    except RuntimeError:
        pass  # already torn down elsewhere — idempotence over ceremony
    finally:
        _initialized_distributed = False
    if was_distributed:
        # ``jax.distributed.initialize`` refuses to run once any backend has
        # been touched, and merely shutting the client down does not reset
        # that — so without this, init→shutdown→init (the elastic re-form
        # round-trip) dies on the second init. Only done when a distributed
        # client actually existed: clearing backends in a plain
        # single-process caller would invalidate every live device array.
        try:
            from jax.extend import backend as jex_backend

            jex_backend.clear_backends()
        except Exception:  # noqa: BLE001 — best-effort across jax versions
            pass


def is_coordinator() -> bool:
    """True on process 0 — the analog of the reference's ``LOCAL_RANK == 0`` /
    rank-0 gating for eval, checkpointing, and logging
    (``pytorch/resnet/main.py:136-137``, ``pytorch/unet/train.py:213``)."""
    return jax.process_index() == 0


def get_system_information() -> dict[str, Any]:
    """Device/host inventory for the run log.

    Replaces the reference's ``get_system_information`` which records world
    size and GPU name at startup (``pytorch/unet/train.py:28-32,356-360``).
    """
    devices = jax.devices()
    return {
        "hostname": socket.gethostname(),
        "python_version": _platform.python_version(),
        "jax_version": jax.__version__,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }
