"""The two forms of multi-head latent attention (DeepSeek-V3's MLA) over a
cached latent.

A position's cache entry is ONE vector ``[c ; k_pe]``: the normed latent
``c`` (``kv_rank`` values) and the rotated key part that every head shares
(``rope`` values). Head ``i``'s key and value come from the latent through
``W_kvb,i = [W_uk,i ; W_uv,i]`` (``[kv_rank, nope + v]``)::

    score_i(t) = scale * (q_nope_i . W_uk,i^T c(t) + q_pe_i . k_pe(t))
    out_i      = sum_t p_i(t) W_uv,i^T c(t)

- :func:`expanded_attention` expands every key position's ``k_nope`` and
  ``v`` once and attends as plain multi-head attention would: the form for
  many queries a key (the uncached forward), where the expansion is shared
  by all of them. :func:`chunk_attention` is the same form for a prefill
  chunk over a cached table, the attention in a Pallas kernel
  (``ops/pallas/latent_prefill.py``) that keeps its scores on chip.
- :func:`absorbed_attention` moves ``W_uk`` onto the query and ``W_uv`` past
  the weighted sum, so the keys are the latent itself, read once for every
  head: the form for one query a row (a decode step), where expanding each
  cached position would cost ``2 * kv_rank * H * (nope + v)`` operations a
  position a step. :func:`paged_absorbed_attention` is the same form over
  the paged latent pools in place, each row's live pages walked through its
  block table in a Pallas kernel (``ops/pallas/latent_decode.py``): what the
  decode step runs; :func:`absorbed_attention` over gathered pages is its
  reference.

The two compute the same function and round differently. Both take the
latent's two parts ``c [B, K, kv_rank]`` and ``k_pe [B, K, rope]`` in the
compute dtype, queries ``q_nope [B, Q, H, nope]`` and ``q_pe [B, Q, H,
rope]`` (rotated), ``w_kvb [kv_rank, H, nope + v]`` and a boolean ``valid
[B, Q, K]``; a query with no valid key yields zeros. Scores and the softmax run in float32, as
``ops.attention.dense_attention``'s do. Returns ``[B, Q, H, v]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning_mpi_tpu.ops.attention import NEG_INF
from deeplearning_mpi_tpu.ops.pallas import latent_decode, latent_prefill
from deeplearning_mpi_tpu.telemetry.trace import annotate


def _softmax(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """``scores [B, H, Q, K]`` f32 under ``valid [B, Q, K]``; all-masked rows zero."""
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    return jnp.where(
        jnp.any(valid, axis=-1)[:, None, :, None], jax.nn.softmax(scores, axis=-1), 0.0
    )


def expanded_attention(
    q_nope: jax.Array, q_pe: jax.Array, c: jax.Array, k_pe: jax.Array,
    w_kvb: jax.Array, *, scale: float, valid: jax.Array,
) -> jax.Array:
    """Every key position expanded to its heads' keys and values, then
    attention over them."""
    nope = q_nope.shape[-1]
    with annotate("attn/expand"):
        kv = jnp.einsum(
            "bkc,chd->bkhd", c, w_kvb.astype(c.dtype),
            preferred_element_type=jnp.float32,
        ).astype(c.dtype)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    with annotate("attn/latent_core"):
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)
        ) * scale
        weights = _softmax(scores, valid)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
    return out.astype(q_nope.dtype)


def chunk_attention(
    q_nope: jax.Array, q_pe: jax.Array, c: jax.Array, k_pe: jax.Array,
    w_kvb: jax.Array, *, scale: float, start: jax.Array,
) -> jax.Array:
    """The expanded form for ONE row's prefill chunk over its table: query
    ``t`` sits at table position ``start + t`` (a traced scalar) and sees
    every key at or before it. The table's latent is expanded head-major
    (``[H, L, nope]`` and ``[H, L, v]``, what the kernel streams a head at a
    time) and attended by :func:`ops.pallas.latent_prefill.chunk_attention`.
    Takes the module's shapes with ``B = 1`` and no ``valid``."""
    nope, dtype = q_nope.shape[-1], c.dtype
    w_kvb = w_kvb.astype(dtype)
    with annotate("attn/expand"):
        k_nope, v = (
            jnp.einsum("kc,chd->hkd", c[0], w, preferred_element_type=jnp.float32).astype(dtype)
            for w in (w_kvb[..., :nope], w_kvb[..., nope:])
        )
    with annotate("attn/latent_core"):
        out = latent_prefill.chunk_attention(
            q_nope[0], q_pe[0], k_nope, v, k_pe[0], scale=scale, start=start
        )
    return out[None]


def absorbed_attention(
    q_nope: jax.Array, q_pe: jax.Array, c: jax.Array, k_pe: jax.Array,
    w_kvb: jax.Array, *, scale: float, valid: jax.Array,
) -> jax.Array:
    """Attention over the latent itself: ``W_uk`` absorbed into the query,
    ``W_uv`` applied to the weighted sum of latents."""
    nope, dtype = q_nope.shape[-1], q_nope.dtype
    w_kvb = w_kvb.astype(dtype)
    w_uk, w_uv = w_kvb[..., :nope], w_kvb[..., nope:]
    with annotate("attn/absorb"):
        q_lat = jnp.einsum(
            "bqhd,chd->bqhc", q_nope, w_uk, preferred_element_type=jnp.float32
        ).astype(dtype)
    with annotate("attn/latent_core"):
        scores = (
            jnp.einsum("bqhc,bkc->bhqk", q_lat, c, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)
        ) * scale
        weights = _softmax(scores, valid)
        o_lat = jnp.einsum(
            "bhqk,bkc->bqhc", weights.astype(c.dtype), c,
            preferred_element_type=jnp.float32,
        ).astype(dtype)
    with annotate("attn/unabsorb"):
        out = jnp.einsum(
            "bqhc,chd->bqhd", o_lat, w_uv, preferred_element_type=jnp.float32
        )
    return out.astype(dtype)


def paged_absorbed_attention(
    q_nope: jax.Array, q_pe: jax.Array, c_pool: jax.Array, kpe_pool: jax.Array,
    layer: int, tables: jax.Array, last: jax.Array, w_kvb: jax.Array, *, scale: float,
) -> jax.Array:
    """:func:`absorbed_attention` for one query a row (``q_nope [B, 1, H,
    nope]``, ``q_pe [B, 1, H, rope]``) over the latent pools in place:
    row ``b`` attends positions ``0 .. last[b]`` of its block table
    ``tables [B, MB]`` in layer ``layer`` of ``c_pool [layers, blocks, BS,
    kv_rank]`` and ``kpe_pool [layers, blocks, rope, BS]``; ``last[b] = -1``
    yields zeros. -> ``[B, 1, H, v]``."""
    nope, dtype = q_nope.shape[-1], q_nope.dtype
    w_kvb = w_kvb.astype(dtype)
    w_uk, w_uv = w_kvb[..., :nope], w_kvb[..., nope:]
    with annotate("attn/absorb"):
        # left in float32: the kernel casts it, as absorbed_attention does
        q_lat = jnp.einsum(
            "bhd,chd->bhc", q_nope[:, 0], w_uk, preferred_element_type=jnp.float32
        )
    with annotate("attn/latent_core"):
        o_lat = latent_decode.latent_decode(
            q_lat, q_pe[:, 0], c_pool, kpe_pool, jnp.int32(layer), tables, last,
            scale=scale, interpret=not latent_decode._on_tpu(),
        )
    with annotate("attn/unabsorb"):
        out = jnp.einsum(
            "bhc,chd->bhd", o_lat, w_uv, preferred_element_type=jnp.float32
        )
    return out.astype(dtype)[:, None]
