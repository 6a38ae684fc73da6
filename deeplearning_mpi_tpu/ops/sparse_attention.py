"""Learned sparse attention: a lightning indexer picks the keys a query attends.

The mechanism of DeepSeek-V3.2-Exp's sparse attention, as models that publish
an ``sa_config`` size it: beside its K and V every position keeps ONE small
indexer key ``kI_s``; a query scores every key at or before it through
``Hi`` small indexer heads,

    I(t, s) = sum_j w_t,j * relu(qI_t,j . kI_s) * Di**-0.5 * Hi**-0.5,

keeps the ``topk`` highest (all of them while fewer exist; ties to the lower
position) and runs ordinary softmax attention over those keys alone, the same
set for every head. Exact ``jax.lax.top_k``: ``approx_max_k`` is a different
result.

The pieces, each under its own scope so a trace or the HLO says where the
time went: :func:`indexer_scores` (``attn/index``), then the SAME selection
in one of two forms (``attn/select``), chosen by who calls from shapes alone:

- :func:`select_topk` gives the kept keys' positions. The caller gathers just
  those K/V rows (``attn/sparse_gather``; the serving engine's decode step,
  through its block table) and :func:`attend_selected` attends them: the
  traffic is ``topk`` rows a query, whatever the context.
- :func:`select_mask` gives the kept keys as a mask over all ``L`` positions,
  with no sort, and :func:`attend_masked` attends contiguous K/V under it:
  one read of the K/V serves every query of the call, which is cheaper as
  soon as the queries are many (a prefill chunk, a whole sequence).

:func:`sparse_attention` is the full-sequence forward. Forward only: the
selection has no gradient and no objective for the indexer is defined here.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from deeplearning_mpi_tpu.ops.attention import NEG_INF
from deeplearning_mpi_tpu.telemetry.trace import annotate

__all__ = [
    "attend_masked",
    "attend_selected",
    "indexer_scores",
    "select_mask",
    "select_topk",
    "sparse_attention",
]


def indexer_scores(q_idx: jax.Array, w: jax.Array, k_idx: jax.Array) -> jax.Array:
    """``I(t, s)`` in float32: ``q_idx [R, T, Hi, Di]``, head weights
    ``w [R, T, Hi]``, keys ``k_idx [R, L, Di]`` -> ``[R, T, L]``. The dot
    takes its operands as they come (the compute dtype) and accumulates in
    float32; ReLU, the head weights and the sum over heads are float32."""
    heads, dim = q_idx.shape[-2:]
    with annotate("attn/index"):
        dots = jnp.einsum(
            "rthd,rld->rthl", q_idx, k_idx, preferred_element_type=jnp.float32
        )
        return jnp.einsum(
            "rthl,rth->rtl", jax.nn.relu(dots), w.astype(jnp.float32)
        ) * (dim**-0.5 * heads**-0.5)


def _masked(scores: jax.Array, visible: jax.Array) -> jax.Array:
    """Scores with -inf where a key is not visible, and one zero: -0.0 and
    0.0 are equal scores, and a sort or a bit pattern would tell them apart."""
    return jnp.where(visible, jnp.where(scores == 0, 0.0, scores), -jnp.inf)


def select_topk(
    scores: jax.Array, visible: jax.Array, topk: int
) -> tuple[jax.Array, jax.Array]:
    """The ``min(topk, L)`` highest-scoring visible keys of each query:
    ``scores``, ``visible`` ``[R, T, L]`` -> ``(ids, kept)`` ``[R, T, K]``.
    ``kept`` is False where a query sees fewer than K keys (its ``ids`` there
    point anywhere). Equal scores go to the lower position."""
    with annotate("attn/select"):
        top, ids = jax.lax.top_k(_masked(scores, visible), min(topk, scores.shape[-1]))
        return ids.astype(jnp.int32), top > -jnp.inf


def _count_search(
    count_at: Callable[[jax.Array], jax.Array],
    lo: jax.Array,
    hi: jax.Array,
    want: jax.Array,
    steps: int,
) -> jax.Array:
    """The largest int32 ``x`` in ``[lo, hi]`` with ``count_at(x) >= want``
    (``count_at`` falls as ``x`` rises; ``count_at(lo) >= want`` holds), by
    ``steps`` halvings, elementwise over ``lo``'s shape."""

    def halve(_: int, bounds: tuple[jax.Array, jax.Array]) -> tuple[jax.Array, jax.Array]:
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)  # ceil of the mean, no overflow
        up = count_at(mid) >= want
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid - 1)

    return jax.lax.fori_loop(0, steps, halve, (lo, hi))[0]


def select_mask(scores: jax.Array, visible: jax.Array, topk: int) -> jax.Array:
    """:func:`select_topk`'s selection as a mask ``[R, T, L]`` over every
    position, without a sort: the K-th highest visible score of each query
    is found by bisection over the order-preserving int32 image of float32
    (32 counting passes), and where scores tie at it, the lowest positions
    fill what is left (17 more passes over positions)."""
    length = scores.shape[-1]
    if length <= topk:
        return visible
    with annotate("attn/select"):
        bits = jax.lax.bitcast_convert_type(_masked(scores, visible), jnp.int32)
        key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)  # rises with the score
        top = jnp.full(scores.shape[:-1], jnp.iinfo(jnp.int32).max, jnp.int32)
        kth = _count_search(
            lambda x: jnp.sum(key >= x[..., None], axis=-1), ~top, top,
            jnp.int32(topk), 32,
        )[..., None]
        above = key > kth
        tied = key == kth
        left = topk - jnp.sum(above, axis=-1)  # >= 1 of the tied are kept
        pos = jnp.arange(length, dtype=jnp.int32)
        # the first position BEFORE which fewer than ``left`` of the tied lie ...
        last = _count_search(
            lambda p: left - jnp.sum(tied & (pos < p[..., None]), axis=-1),
            jnp.zeros_like(top), jnp.full_like(top, length - 1), jnp.int32(1),
            max(length - 1, 1).bit_length(),
        )[..., None]
        # ... is the last tied position kept
        return (above | (tied & (pos <= last))) & visible


def _softmax_over_kept(s: jax.Array, keep: jax.Array) -> jax.Array:
    """Softmax of float32 scores over the keys ``keep`` (broadcastable to
    ``s``) lets in; a query it lets nothing in for gets zeros, not the
    uniform average an all-masked softmax renormalises to."""
    return jnp.where(
        jnp.any(keep, axis=-1, keepdims=True),
        jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1),
        0.0,
    )


def attend_masked(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array
) -> jax.Array:
    """Softmax attention of ``q [R, T, H, D]`` over contiguous
    ``k, v [R, L, Hkv, D]`` (grouped heads consumed natively) under
    ``mask [R, T, L]``, the same for every head. float32 scores and softmax;
    a query with nothing kept gets zeros. -> ``[R, T, H, D]``."""
    rows, seq, heads, dim = q.shape
    kv_heads = k.shape[-2]
    qg = q.reshape(rows, seq, kv_heads, heads // kv_heads, dim)
    s = jnp.einsum(
        "rthgd,rlhd->rhgtl", qg, k, preferred_element_type=jnp.float32
    ) * dim**-0.5
    w = _softmax_over_kept(s, mask[:, None, None])
    out = jnp.einsum(
        "rhgtl,rlhd->rthgd", w.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(rows, seq, heads, dim).astype(q.dtype)


def attend_selected(
    q: jax.Array, k_sel: jax.Array, v_sel: jax.Array, kept: jax.Array
) -> jax.Array:
    """Softmax attention of ``q [R, T, H, D]`` over its own selected rows
    ``k_sel, v_sel [R, T, K, Hkv, D]`` (grouped heads consumed natively),
    masked by ``kept [R, T, K]``. float32 scores and softmax; a query with
    nothing kept (an inactive row) gets zeros. -> ``[R, T, H, D]``."""
    rows, seq, heads, dim = q.shape
    kv_heads = k_sel.shape[-2]
    qg = q.reshape(rows, seq, kv_heads, heads // kv_heads, dim)
    s = jnp.einsum(
        "rthgd,rtkhd->rthgk", qg, k_sel, preferred_element_type=jnp.float32
    ) * dim**-0.5
    w = _softmax_over_kept(s, kept[:, :, None, None, :])
    out = jnp.einsum(
        "rthgk,rtkhd->rthgd", w.astype(v_sel.dtype), v_sel,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(rows, seq, heads, dim).astype(q.dtype)


def sparse_attention(
    q: jax.Array,      # [B, S, H, D]
    k: jax.Array,      # [B, S, Hkv, D]
    v: jax.Array,      # [B, S, Hkv, D]
    q_idx: jax.Array,  # [B, S, Hi, Di]
    w: jax.Array,      # [B, S, Hi]
    k_idx: jax.Array,  # [B, S, Di]
    topk: int,
) -> jax.Array:
    """Causal sparse attention of a whole sequence over its own keys: query
    ``t`` attends the ``topk`` keys ``s <= t`` its indexer scores highest.
    Every query's score row is held at once (``[B, S, S]``, the indexer's
    products ``[B, S, Hi, S]``): the full-sequence forward of
    ``TransformerLM``, not a long-context path (the serving engine tiles)."""
    seq = q.shape[1]
    pos = jnp.arange(seq, dtype=jnp.int32)
    visible = jnp.broadcast_to(pos[None, :] <= pos[:, None], (q.shape[0], seq, seq))
    mask = select_mask(indexer_scores(q_idx, w, k_idx), visible, topk)
    with annotate("attn/core"):
        return attend_masked(q, k, v, mask)
