"""Every Pallas entry point lowers for TPU from a CPU process.

``lowering_platforms=("tpu",)`` runs the jaxpr -> Mosaic lowering without a
chip (seconds), at the shapes the main path and ``chip_smoke.py`` use. It
catches a jax upgrade that breaks the lowering before any chip time is spent.
It says nothing about Mosaic's own compile (layout inference, VMEM): that is
``chip_smoke.py``'s kernel phase.
"""

import jax
import jax.numpy as jnp
import pytest

from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_bhsd,
)
from deeplearning_mpi_tpu.ops.pallas.flash_decode import flash_decode

BF16 = jnp.bfloat16
# The 110M LM: batch 8, 12 heads x 64, seq 2048; decode over an 8k buffer.
B, H, S, D, L = 8, 12, 2048, 64, 8192


def _with_grads(attn):
    def fn(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return (o, *vjp(do))
    return fn


def _mosaic_calls(fn, *avals) -> int:
    lowered = jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize(
    "attn, shape, window",
    [
        (flash_attention_bhsd, (B, H, S, D), None),
        (flash_attention_bhsd, (B, H, S, D), 512),
        (flash_attention_bhsd, (4, H, S, 128), None),
        (flash_attention, (B, S, H, D), None),
    ],
    ids=["bhsd", "bhsd_windowed", "bhsd_d128", "bshd"],
)
def test_flash_fwd_and_bwd_lower(attn, shape, window):
    aval = jax.ShapeDtypeStruct(shape, BF16)
    calls = _mosaic_calls(
        _with_grads(
            lambda q, k, v: attn(q, k, v, window=window, interpret=False)
        ),
        aval, aval, aval, aval,
    )
    assert calls == 3  # fwd, dq, dkv


@pytest.mark.parametrize("batch", [1, 8, 32])
@pytest.mark.parametrize("variant", ["bf16", "windowed", "int8"])
def test_flash_decode_lowers(batch, variant):
    q = jax.ShapeDtypeStruct((batch, 1, H, D), BF16)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32)
    kv_dtype = jnp.int8 if variant == "int8" else BF16
    kv = jax.ShapeDtypeStruct((batch, L, H, D), kv_dtype)
    if variant == "int8":
        scale = jax.ShapeDtypeStruct((batch, L, H), jnp.float32)
        calls = _mosaic_calls(
            lambda q, k, ks, v, vs, i: flash_decode(
                q, k, v, i, k_scale=ks, v_scale=vs, interpret=False),
            q, kv, scale, kv, scale, idx,
        )
    else:
        window = 1024 if variant == "windowed" else None
        calls = _mosaic_calls(
            lambda q, k, v, i: flash_decode(
                q, k, v, i, window=window, interpret=False),
            q, kv, kv, idx,
        )
    assert calls == 1


def test_flash_on_a_mesh_lowers_only_under_shard_map(monkeypatch):
    """GSPMD cannot partition a Mosaic call, so ``--attention flash`` on more
    than one device must run under shard_map (``make_flash_attention_fn``).
    The interpreter hides this — it lowers to plain HLO — so the kernels are
    traced here as on a TPU backend. When jax learns to partition Mosaic
    calls the second half fails, and the wrapper can go."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning_mpi_tpu.parallel import make_flash_attention_fn
    from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = create_mesh(MeshSpec(data=4, model=2))
    aval = jax.ShapeDtypeStruct(
        (B, H, S, D), BF16,
        sharding=NamedSharding(mesh, P("data", "model", None, None)),
    )
    sharded = make_flash_attention_fn(mesh)
    assert sharded.layout == "bhsd"
    assert _mosaic_calls(_with_grads(sharded), aval, aval, aval, aval) == 3
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _mosaic_calls(
            _with_grads(flash_attention_bhsd), aval, aval, aval, aval
        )
