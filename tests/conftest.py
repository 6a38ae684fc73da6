"""Test harness: 8 virtual CPU devices, no TPU required.

The reference tests multi-node behavior without a GPU cluster by running N
Gloo processes on one machine (``pytorch/hello_world/hello_world.py:19-22,44``
— SURVEY.md §4). The JAX equivalent is a single process with N fake CPU
devices via ``--xla_force_host_platform_device_count``, giving every mesh /
collective / sharding test a real 8-way SPMD execution on any machine.

Must run before jax is imported: the env vars below are read at import, and
worker subprocesses the tests spawn inherit them.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent compilation cache: the suite's cost is dominated by XLA:CPU
# compiles of many distinct jitted programs, and the cache works for CPU
# executables too (measured: a tiny-ResNet init+apply drops 21.7s -> 4.0s
# process wall on the second run). An operator's JAX_COMPILATION_CACHE_DIR
# stands; otherwise the first run populates `.jax_cache/` (gitignored, the
# same directory compiler.cache.configure picks) and every later run pays
# only trace time for unchanged programs. A changed program gets a new key,
# so the cache can't mask a code change. Programs under 0.3 s are not worth
# the disk round trip on CPU.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_virtual_mesh():
    assert jax.device_count() == 8, (
        "tests require 8 virtual CPU devices; got "
        f"{jax.device_count()} on {jax.devices()[0].platform}"
    )
    yield


@pytest.fixture()
def mesh():
    from deeplearning_mpi_tpu.runtime.mesh import create_mesh

    return create_mesh()
