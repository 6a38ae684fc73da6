"""Scaled dot-product attention (dense reference implementation).

The reference contains no attention at all — both workloads are CNNs
(``pytorch/unet/model.py:51-81``, ``pytorch/resnet/main.py:40``; SURVEY.md
§5.7) — but long-context support is first-class in this framework, so
attention is a core op with three interchangeable implementations:

- :func:`dense_attention` (here) — the O(S²)-memory einsum reference, used
  on short sequences, on CPU, and as the numerical oracle in tests;
- ``ops.pallas.flash_attention`` — the tiled online-softmax Pallas TPU
  kernel (O(S) memory, MXU-shaped blocks);
- ``parallel.ring_attention`` — sequence-parallel blockwise attention over
  the mesh ``seq`` axis, rotating K/V shards with ``ppermute``.

All three share this op's conventions: inputs ``[batch, seq, heads, head_dim]``
("BSHD"), softmax accumulated in float32 regardless of input dtype, output in
the input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30  # large-negative mask value; -inf breaks softmax when a row is fully masked


def repeat_kv(x: jax.Array, n_rep: int, *, axis: int = -2) -> jax.Array:
    """Repeat each KV head ``n_rep`` times along the head axis (GQA → MHA).

    Grouped-query attention stores K/V at ``num_kv_heads < num_heads``; the
    full-sequence cores (dense, flash, ring) expect matching head counts, so
    the model repeats K/V immediately before calling them. That is the right
    trade for *training*: full-sequence attention is MXU-bound, and GQA's win
    there is the smaller K/V projections — while *decode* is HBM-bound, so
    :func:`decode_attention` consumes the grouped buffers natively instead
    of repeating (reads ``num_kv_heads``, not ``num_heads``, rows per
    position). ``axis=-2`` is the BSHD head axis; BHSD callers pass 1.
    """
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=axis)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
) -> jax.Array:
    """Full-materialization attention over ``[B, S, H, D]`` inputs.

    ``q_offset``/``kv_offset`` are the absolute positions of the first query /
    key row — used by the blockwise/ring implementations, which call this on
    sequence *shards* and need causal masking in global coordinates.

    ``window`` (sliding-window / local attention, Mistral-style): each query
    attends only its last ``window`` keys (self included) — requires
    ``causal`` since the window is defined against the causal past. This is
    the numerical oracle for the windowed flash kernel
    (``ops.pallas.flash_attention(window=...)``).
    """
    if window is not None and not causal:
        raise ValueError("window attention is causal by definition; pass causal=True")
    *_, q_len, _, head_dim = q.shape
    kv_len = k.shape[-3]
    scale = head_dim**-0.5
    # [B, H, Sq, Skv] scores in f32: bf16 logits lose too much softmax precision.
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    weights = None
    if causal:
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
        k_pos = kv_offset + jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
        valid = q_pos >= k_pos
        if window is not None:
            valid &= q_pos - k_pos < window
        scores = jnp.where(valid, scores, NEG_INF)
        # A query row with NO valid key (possible on blockwise shards that are
        # entirely in the row's future) must contribute zero, not a uniform
        # average of V — softmax alone would renormalize the all-masked row.
        weights = jnp.where(
            jnp.any(valid, axis=-1)[:, None], jax.nn.softmax(scores, axis=-1), 0.0
        )
    if weights is None:
        weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _tuned_decode_schedule(
    shape: tuple[int, ...], dtype,
) -> tuple[bool, int | None]:
    """(use_kernel, block) from the autotuner's DB for this ``[B, L, Hkv,
    D]`` buffer — ``(False, None)`` when untuned/unavailable, so
    ``use_kernel=None`` keeps today's einsum/walk behavior without a DB."""
    try:
        from deeplearning_mpi_tpu.compiler.autotune import (
            tuned_decode_schedule,
        )

        tuned = tuned_decode_schedule(tuple(shape), dtype)
    except Exception:
        return False, None
    if not tuned:
        return False, None
    return tuned["schedule"] == "kernel", tuned.get("block")


#: Buffers at or below this length take the one-shot masked path: measured
#: on a v5e (tools/bench_decode.py, device-looped timing), the single fused
#: einsum runs at the HBM roofline (~72 us/token flat at B8 H12 D64
#: max_len 2048) while the blockwise while-loop walk pays ~40 us per
#: iteration (~45% of roofline at block 512) — it only beats reading the
#: whole buffer once the unfilled tail it skips outweighs that derate,
#: i.e. on long buffers.
DECODE_DENSE_MAX = 4096


def decode_attention(
    q: jax.Array,
    k_buf: jax.Array,
    v_buf: jax.Array,
    index: jax.Array,
    *,
    block: int = 2048,
    dense_max: int = DECODE_DENSE_MAX,
    window: int | None = None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """One KV-cached decode step over the filled prefix of the cache.

    ``q`` is ``[B, 1, H, D]`` (the single new token, RoPE applied);
    ``k_buf``/``v_buf`` are the ``[B, max_len, Hkv, D]`` cache buffers with
    positions ``0..index`` (inclusive) filled. ``Hkv`` may be a divisor of
    ``H`` (grouped-query attention): the grouped buffers are read as-is —
    never repeated to ``H`` — so decode HBM traffic per token scales with
    ``Hkv``, compounding GQA's cache-size saving.

    Two schedules, chosen at TRACE time on the static buffer length:

    - ``max_len <= dense_max``: ONE masked grouped einsum over the whole
      buffer. Reads unfilled rows, but as a single fused op it runs at the
      HBM roofline — measured 1.3-2.3x faster than the blockwise walk for
      fills above ~1/3 of a 2k buffer (tools/bench_decode.py).
    - longer buffers: the flash-decoding walk — ``block``-sized chunks
      under a ``lax.fori_loop`` whose trip count ``ceil((index+1)/block)``
      is *traced* (XLA lowers a while loop), so blocks past the prefix are
      neither read nor scored and per-token HBM traffic is O(index), not
      O(max_len). The flash-style ``(acc, m, l)`` accumulator keeps softmax
      exact across chunks in f32. The 2048 default block amortizes the
      measured ~40 us/iteration loop overhead.

    ``window`` (sliding-window models): the query attends only cache
    positions ``index-window+1 .. index``. The blockwise walk then *starts*
    at the window's first block instead of 0, so per-token HBM traffic is
    O(window) however long the generation has run — decode cost stops
    growing with context, the inference-side half of the sliding-window
    trade.

    ``use_kernel``: the fused Pallas decode kernel
    (``ops.pallas.flash_decode``) for long buffers — one grid instead of
    the walk's ``lax.fori_loop`` (whose ~40 µs/iteration host overhead
    caps the walk at ~45% of the HBM roofline, PERF_ANALYSIS §9), keeping
    O(index) — O(window) for sliding-window models — HBM traffic via its
    two-sided clamped index map. ``True`` selects it when the buffer tiles
    (the interpreter off-TPU); ``False`` keeps the walk; ``None`` consults
    the autotuner's tuning DB for this buffer's (shape, dtype, backend) —
    a recorded ``flash_decode`` winner selects the kernel at its measured
    block, an untuned shape keeps the walk (``compiler/autotune.py``;
    ``make tune-smoke`` exercises the loop end-to-end).

    Not differentiable (dynamic trip count) — decode is inference-only.
    """
    batch, q_len, heads, head_dim = q.shape
    if q_len != 1:
        raise ValueError(f"decode_attention takes one query token, got {q_len}")
    length, kv_heads = k_buf.shape[1], k_buf.shape[2]
    if heads % kv_heads:
        raise ValueError(
            f"query heads ({heads}) must be a multiple of KV heads ({kv_heads})"
        )
    group = heads // kv_heads
    scale = head_dim**-0.5

    if length <= dense_max:
        # Input-dtype dot with an f32 accumulator — the same formulation
        # the roofline measurement used. An astype(f32) on k_buf instead
        # would risk materializing a double-width copy of the whole cache,
        # exactly the HBM bytes this path is chosen to minimize.
        qg = q[:, 0].reshape(batch, kv_heads, group, head_dim)
        s = jnp.einsum(
            "bhgd,bkhd->bhgk", qg, k_buf,
            preferred_element_type=jnp.float32,
        ) * scale  # [B, Hkv, G, L]
        pos = jnp.arange(length, dtype=jnp.int32)
        valid = pos <= index
        if window is not None:
            valid &= pos > index - window
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bhgk,bkhd->bhgd", w.astype(v_buf.dtype), v_buf,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(batch, heads, head_dim)[:, None].astype(q.dtype)
    if use_kernel is None:
        use_kernel, tuned_block = _tuned_decode_schedule(
            k_buf.shape, k_buf.dtype
        )
        if tuned_block:
            block = tuned_block
    if use_kernel:
        from deeplearning_mpi_tpu.ops.pallas.flash_decode import (
            flash_decode,
            kernel_decode_block,
        )

        fitted = kernel_decode_block(block, k_buf.shape, k_buf.dtype)
        if fitted is not None:
            return flash_decode(
                q, k_buf, v_buf, index, block=fitted, window=window
            )
    # Blocks stay full-size whatever the buffer length (a CLI cache is
    # prompt+max_new — arbitrary): the final block's start is clamped back
    # so it never runs off the buffer, and rows it re-reads from the
    # previous block are masked out of the softmax. Shrinking the block to
    # a divisor instead can collapse to near-scalar slices (e.g. 2500 % 512
    # chains down to 4) and lose to the dense path it replaces.
    b = min(block, length)
    n_blocks = (index + b) // b  # ceil((index+1)/b), traced
    # [B, Hkv, G, D]: query heads grouped by the KV head they share.
    q32 = (q[:, 0].astype(jnp.float32) * scale).reshape(
        batch, kv_heads, group, head_dim
    )

    def body(j, carry):
        acc, m, l = carry
        start = jnp.minimum(j * b, length - b)
        k_blk = lax.dynamic_slice(
            k_buf, (0, start, 0, 0), (batch, b, kv_heads, head_dim)
        )
        v_blk = lax.dynamic_slice(
            v_buf, (0, start, 0, 0), (batch, b, kv_heads, head_dim)
        )
        s = jnp.einsum(
            "bhgd,bkhd->bhgk", q32, k_blk.astype(jnp.float32)
        )  # [B, Hkv, G, b]
        pos = start + jnp.arange(b, dtype=jnp.int32)
        # Lower bound deduplicates the clamped tail's overlap with block j-1.
        valid = (pos >= j * b) & (pos <= index)
        if window is not None:
            valid &= pos > index - window
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum(
            "bhgk,bkhd->bhgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return acc * alpha[..., None] + pv, m_new, l * alpha + jnp.sum(p, axis=-1)

    # Windowed decode never reads blocks wholly before the window: start the
    # walk at the window's first block (traced, like the trip count).
    j_start = (
        jnp.maximum(index - window + 1, 0) // b if window is not None else 0
    )
    acc, _, l = lax.fori_loop(
        j_start, n_blocks, body,
        (
            jnp.zeros((batch, kv_heads, group, head_dim), jnp.float32),
            jnp.full((batch, kv_heads, group), NEG_INF, jnp.float32),
            jnp.zeros((batch, kv_heads, group), jnp.float32),
        ),
    )
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.reshape(batch, heads, head_dim)[:, None].astype(q.dtype)


def batched_decode_attention(
    q: jax.Array,
    k_buf: jax.Array,
    v_buf: jax.Array,
    index: jax.Array,
    *,
    window: int | None = None,
) -> jax.Array:
    """One decode step where every row sits at its OWN fill level.

    :func:`decode_attention` serves the single-program CLI path: one scalar
    ``index`` because the whole batch decodes in lockstep. A continuous-
    batching engine breaks that assumption by design — each slot holds a
    different sequence, so ``index`` here is ``[B]`` int32 (row ``b`` attends
    cache positions ``0..index[b]``; negative = inactive row, output zeros).
    Shapes otherwise match: ``q`` ``[B, 1, H, D]``, grouped buffers
    ``[B, L, Hkv, D]``, grouped heads consumed natively (no ``repeat_kv``).

    ONE masked grouped einsum over the whole buffer, the serving engine's
    only decode core: its buffers are the gathered pages of
    ``serving.kv_pool`` (the step's live rows x live width,
    ``ServingEngine._decode_shape``), so there is no unfilled tail worth a
    walk. On the v5e the decode program it sits in reads 72.2% of its bytes
    bound at 8 rows x 128 blocks (``serve_decode_roofline``; ledger, PR 31).
    What would beat it is attention that reads the pool through the block
    table without the gather (ROADMAP S1) — a new kernel, not a schedule of
    this function.

    Not differentiable; decode is inference-only.
    """
    batch, q_len, heads, head_dim = q.shape
    if q_len != 1:
        raise ValueError(f"batched_decode_attention takes one query token, got {q_len}")
    length, kv_heads = k_buf.shape[1], k_buf.shape[2]
    if heads % kv_heads:
        raise ValueError(
            f"query heads ({heads}) must be a multiple of KV heads ({kv_heads})"
        )
    index = jnp.asarray(index, jnp.int32)
    if index.shape != (batch,):
        raise ValueError(
            f"index must be [{batch}] (one fill level per row), got {index.shape}"
        )
    group = heads // kv_heads
    scale = head_dim**-0.5
    qg = q[:, 0].reshape(batch, kv_heads, group, head_dim)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, k_buf, preferred_element_type=jnp.float32
    ) * scale  # [B, Hkv, G, L]
    pos = jnp.arange(length, dtype=jnp.int32)
    valid = pos[None, :] <= index[:, None]  # [B, L] — per-row prefix
    if window is not None:
        valid &= pos[None, :] > (index[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    # An inactive row (index < 0) has NO valid key: zero its output rather
    # than letting softmax renormalize the all-masked row into a uniform
    # average of garbage V rows (same rule as dense_attention's empty-row
    # guard).
    w = jnp.where(
        jnp.any(valid, axis=-1)[:, None, None, None],
        jax.nn.softmax(s, axis=-1),
        0.0,
    )
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", w.astype(v_buf.dtype), v_buf,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(batch, heads, head_dim)[:, None].astype(q.dtype)
