"""Fused flash-decode kernel: one grid over the KV cache's filled prefix.

The long-buffer decode schedule (`ops.attention.decode_attention`'s blockwise
walk) pays a measured ~40 µs of loop overhead per `lax.fori_loop` iteration —
~45% of the HBM roofline at block 512, amortized but not gone at 2048
(`docs/PERF_ANALYSIS.md` §9). This kernel replaces the host-orchestrated walk
with ONE `pallas_call`: the kv-block axis is a sequential grid dimension, the
online-softmax accumulator lives in VMEM scratch, and the dynamic fill level
rides a scalar-prefetch argument:

- the **index map clamps both ends**: out-of-prefix grid steps collapse
  onto the last filled block, and (for sliding-window models) pre-window
  steps onto the window's first block — Mosaic skips the DMA when
  consecutive steps map to the same block, so HBM traffic stays O(index)
  (O(window) with a window), the walk's defining advantage over the
  read-everything dense path;
- the **compute gate** (`pl.when(j_lo <= j < n_valid)`) skips their FLOPs;
- masking inside the boundary blocks uses the prefetched `index` scalar
  (both the filled-prefix end and the window's trailing edge).

The prefetched index is PER ROW (`[B]`; a scalar broadcasts): every batch
row clamps, gates, and masks against its own fill level. That is the shape
continuous batching needs — a serving engine's decode slots all sit at
different sequence lengths, and one fixed-shape kernel call covers them
(`serving/engine.py` gathers each slot's pages and hands the per-slot
lengths straight in).

Layout: the cache is BSHD (`[B, L, Hkv, D]`) and the kernel blocks over L
only, keeping each row's full `Hkv x D` contiguous — the same access pattern
the dense einsum path achieves roofline with. Grouped-query heads are
consumed natively (Hkv < H reads Hkv rows, like the walk). No reference
analog (the reference has no attention at all — SURVEY.md §5.7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning_mpi_tpu.runtime.compat import tpu_compiler_params
from deeplearning_mpi_tpu.telemetry.trace import annotate

from deeplearning_mpi_tpu.ops.attention import NEG_INF


def _window_start_block(index, window: int, block: int):
    """First cache block intersecting the window — ONE definition shared by
    the kernel's compute gate and the index map's clamp: if the two drift,
    a gated-on grid step could score a block whose DMA was collapsed onto
    a different one (silently wrong output)."""
    return jnp.maximum(index - window + 1, 0) // block


def quantize_kv(buf: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row-per-head int8 for KV cache buffers.

    ``[B, L, Hkv, D]`` float → ``(int8 [B, L, Hkv, D], f32 scales
    [B, L, Hkv])``. Halves the decode phase's per-row cache bytes — the
    term batching cannot amortize (PERF_ANALYSIS §10: ~75 MB/step/row at
    2k MHA vs the 220 MB batch-invariant weight read) — at a per-element
    quantization error ≤ scale/2, the same contract as the weight-only
    int8 kernels (`ops/quant.py`).
    """
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)), axis=-1)
    scales = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(buf.astype(jnp.float32) / scales[..., None])
    return q.astype(jnp.int8), scales


#: VMEM the K and V blocks may take, double-buffered (4 buffers). The v5e's
#: scoped-VMEM limit is 16 MiB and a block is padded to its dtype's tile
#: (Hkv 12 x D 64 in bf16 pads to 16 x 128: 4 KiB a row). At 1024 rows the
#: buffers alone are 16 MiB and Mosaic refuses the kernel by 24 KB; at 512
#: (8 MiB) it compiles in 6.6 s and runs in 16.7 ms a call; at 256 (4 MiB)
#: it compiles in 1.8 s and runs in 2.4 ms (B8 L8192 bf16, one TPU v5 lite,
#: chip run of PR 21) — the per-head strided slices of the block are relaid
#: out on the VMEM stack beside it.
_DECODE_KV_VMEM_BUDGET = 4 * 1024 * 1024

#: Module default for the KV block — what an untuned :func:`flash_decode`
#: call resolves to: the most rows the budget above allows (a tile row is
#: 4 KiB in every dtype), cut further by :func:`fit_decode_vmem` for wider
#: heads. A tuning DB entry for the buffer's exact (shape, dtype, backend)
#: overrides it; an explicit ``block=`` kwarg overrides everything and is
#: used as given (a block Mosaic refuses is then a compile error, loudly).
DEFAULT_DECODE_BLOCK = 256


def fit_decode_vmem(block: int, kv_heads: int, head_dim: int, dtype) -> int:
    """Halve ``block`` until the double-buffered, tile-padded K and V blocks
    fit :data:`_DECODE_KV_VMEM_BUDGET` (halving keeps a power-of-two block a
    divisor of the buffer it tiled)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize  # rows of one (sublanes, 128) tile
    row_bytes = (
        -(-kv_heads // sublanes) * sublanes * -(-head_dim // 128) * 128
        * itemsize
    )
    while block > 8 and 4 * block * row_bytes > _DECODE_KV_VMEM_BUDGET:
        block //= 2
    return block


def resolve_decode_block(block: int | None, shape: tuple[int, ...], dtype) -> int:
    """Block resolution: explicit kwarg > tuning-DB ``flash_decode`` entry
    for this ``[B, L, Hkv, D]`` buffer > the module default cut to VMEM
    (:func:`fit_decode_vmem`). Never raises."""
    if block is not None:
        return block
    try:
        from deeplearning_mpi_tpu.compiler.autotune import (
            tuned_decode_schedule,
        )

        tuned = tuned_decode_schedule(tuple(shape), dtype)
        if tuned and tuned.get("block"):
            return int(tuned["block"])
    except Exception:
        pass
    return fit_decode_vmem(DEFAULT_DECODE_BLOCK, shape[2], shape[3], dtype)


def _decode_kernel(
    idx_ref, q_ref, *refs,
    block: int, kv_heads: int, group: int, scale: float,
    window: int | None = None, quantized: bool = False,
):
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc, m, l = refs
    else:
        (k_ref, v_ref, o_ref, acc, m, l), ks_ref, vs_ref = refs, None, None
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    index = idx_ref[pl.program_id(0)]  # this row's fill level
    n_valid = (index + block) // block  # blocks with >= 1 filled row
    run = j < n_valid
    if window is not None:
        # Sliding-window models: blocks wholly before the window are
        # skipped (their DMAs collapse onto the window's first block via
        # the clamped index map) — O(window) traffic per token, like the
        # walk's start-block skip.
        run = run & (j >= _window_start_block(index, window, block))

    @pl.when(run)
    def _update():
        # Rows beyond the filled prefix are masked (only the boundary block
        # has any; interior blocks mask nothing and the where folds away).
        pos = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        valid = pos <= index  # [1, block]
        if window is not None:
            valid &= pos > index - window
        for h in range(kv_heads):
            q_h = q_ref[0, 0, h * group : (h + 1) * group, :]  # [G, D]
            # int8 buffers: cast to the q dtype for fast MXU dots and
            # factor the per-row scales OUT of the contractions (the
            # QuantDense dot-then-scale form, ops/quant.py — bf16's 8
            # mantissa bits represent ±127 exactly): the K scales multiply
            # the score columns after the dot, the V scales fold into p
            # before the V dot — O(block) scale work, not O(block·D).
            k_h = k_ref[0, :, h, :].astype(q_h.dtype)  # [block, D]
            v_h = v_ref[0, :, h, :].astype(q_h.dtype)
            s = lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, block]
            if quantized:
                s = s * ks_ref[0, :, h][None, :]
            s = jnp.where(valid, s, NEG_INF)
            rows = slice(h * group, (h + 1) * group)
            m_prev = m[rows, :1]  # [G, 1]
            l_prev = l[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)  # finite NEG_INF ⇒ re-zero masked
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, :, h][None, :]
            pv = lax.dot_general(
                p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, D]
            acc[rows, :] = acc[rows, :] * alpha + pv
            m[rows, :] = jnp.broadcast_to(m_new, (group, m.shape[1]))
            l[rows, :] = jnp.broadcast_to(l_new, (group, l.shape[1]))

    @pl.when(j == nb - 1)
    def _finalize():
        # Block 0 always holds >= 1 filled row (index >= 0), so l > 0 on
        # the real rows; scratch is sublane-padded, so slice them out.
        heads = kv_heads * group
        o_ref[0, 0] = (acc[:heads, :] / l[:heads, :1]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,
    k_buf: jax.Array,
    v_buf: jax.Array,
    index: jax.Array,
    *,
    block: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One fused decode step over the cache's filled prefix.

    Same contract as the blockwise walk in
    :func:`~deeplearning_mpi_tpu.ops.attention.decode_attention`: ``q``
    ``[B, 1, H, D]``, grouped cache buffers ``[B, L, Hkv, D]``, positions
    ``0..index`` filled (``window``: attend the last ``window`` of them
    only); returns ``[B, 1, H, D]``. Caller guarantees ``L % block == 0``
    (see :func:`decode_block_fits`). ``block=None`` resolves through
    :func:`resolve_decode_block` — a tuning-DB entry for this buffer shape
    when installed, else the module default cut to what VMEM holds.

    ``index`` may be a scalar (every row at the same fill — the single-
    sequence CLI path) or ``[B]`` (per-row fills — continuous-batching
    slots); HBM traffic stays O(own index) per row either way.

    ``k_scale``/``v_scale`` (``[B, L, Hkv]`` f32, from :func:`quantize_kv`)
    switch the buffers to int8: the kernel reads half the cache bytes per
    step — the batched-decode term §10's roofline says batching can't
    amortize — and dequantizes per block in VMEM.
    """
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if quantized and (k_buf.dtype != jnp.int8 or v_buf.dtype != jnp.int8):
        raise ValueError(
            f"scales given but buffers are not int8 (k={k_buf.dtype}, "
            f"v={v_buf.dtype}) — quantize BOTH with quantize_kv first"
        )
    batch, q_len, heads, head_dim = q.shape
    length, kv_heads = k_buf.shape[1], k_buf.shape[2]
    group = heads // kv_heads
    block = resolve_decode_block(block, k_buf.shape, k_buf.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block < 1 or length % block:
        raise ValueError(
            f"flash_decode: block {block} does not tile the {length}-row "
            "cache buffer (see decode_block_fits)"
        )
    n_blocks = length // block
    index = jnp.asarray(index, jnp.int32)
    if index.ndim == 0:
        index = jnp.broadcast_to(index[None], (batch,))
    elif index.shape != (batch,):
        raise ValueError(
            f"index must be a scalar or [{batch}] (one fill level per row), "
            f"got shape {index.shape}"
        )

    def q_map(b, j, idx_ref):
        del idx_ref, j
        return (b, 0, 0, 0)

    def kv_map(b, j, idx_ref):
        # Index maps receive the prefetched scalars AFTER the grid indices,
        # as a ([B],)-shaped ref: row b clamps against its own fill level.
        idx = idx_ref[b]
        n_valid = (idx + block) // block
        # Clamp both ends: steps past the prefix revisit the last filled
        # block, pre-window steps the window's first block — Mosaic skips
        # the DMA on consecutive identical indices either way.
        j_eff = jnp.minimum(j, n_valid - 1)
        if window is not None:
            j_eff = jnp.maximum(
                j_eff, _window_start_block(idx, window, block)
            )
        return (b, j_eff, 0, 0)

    kv_spec = pl.BlockSpec((1, block, kv_heads, head_dim), kv_map,
                           memory_space=pltpu.VMEM)
    scale_spec = pl.BlockSpec(
        (1, block, kv_heads), lambda b, j, idx_ref: kv_map(b, j, idx_ref)[:3],
        memory_space=pltpu.VMEM,
    )
    in_specs = [
        pl.BlockSpec((1, 1, heads, head_dim), q_map, memory_space=pltpu.VMEM),
        kv_spec,
    ]
    operands = [q, k_buf]
    if quantized:
        in_specs.append(scale_spec)
        operands.append(k_scale)
    in_specs.append(kv_spec)
    operands.append(v_buf)
    if quantized:
        in_specs.append(scale_spec)
        operands.append(v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, heads, head_dim), q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            # Rows padded to the 8-row sublane (H = 12 at the 110M config);
            # the kernel touches only the first `heads` rows.
            pltpu.VMEM((-(-heads // 8) * 8, head_dim), jnp.float32),  # acc
            pltpu.VMEM((-(-heads // 8) * 8, 128), jnp.float32),  # running max
            pltpu.VMEM((-(-heads // 8) * 8, 128), jnp.float32),  # denom
        ],
    )
    with annotate("pallas/flash_decode"):
        return pl.pallas_call(
            functools.partial(
                _decode_kernel,
                block=block, kv_heads=kv_heads, group=group,
                scale=head_dim**-0.5, window=window, quantized=quantized,
            ),
            out_shape=jax.ShapeDtypeStruct((batch, 1, heads, head_dim), q.dtype),
            grid_spec=grid_spec,
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(index, *operands)


#: Smallest block the kernel accepts: below this the grid degenerates into
#: the near-scalar slicing the walk's full-size-block design exists to
#: avoid (attention.py's non-dividing-length comment) — fall back to the
#: walk instead of silently running a 100+-step tiny-block grid.
_MIN_DECODE_BLOCK = 256


def kernel_decode_block(block: int, shape: tuple[int, ...], dtype) -> int | None:
    """The block the dispatchers hand the kernel for a ``[B, L, Hkv, D]``
    buffer: ``block`` cut to VMEM (:func:`fit_decode_vmem`), then to a
    divisor of ``L`` (:func:`decode_block_fits`); None = take the walk."""
    _, length, kv_heads, head_dim = shape
    return decode_block_fits(
        fit_decode_vmem(block, kv_heads, head_dim, dtype), length
    )


def decode_block_fits(block: int, length: int) -> int | None:
    """Largest ``fit_block``-shrunk block that tiles ``length``, or None.

    Decode buffers are ``prompt + max_new`` (arbitrary), so non-tileable
    lengths (and lengths only tileable by degenerate tiny blocks) fall
    back to the XLA walk rather than constraining the CLI.
    """
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import fit_block

    b = fit_block(block, length)
    # Floor scales down with an explicitly small requested block (tests use
    # 16-row blocks on tiny buffers); the dispatcher's production request
    # gets the full floor.
    if length % b or b % 8 or b < min(_MIN_DECODE_BLOCK, block):
        return None
    return b
