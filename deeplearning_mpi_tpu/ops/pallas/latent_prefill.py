"""Expanded latent attention for one prefill chunk: a Pallas TPU kernel.

A chunk of ``C`` queries at table positions ``start .. start + C - 1``
attends every key at or before its own position in a table of ``L``
positions whose latent is expanded to every head's key part and value
(``k_nope [H, L, nope]``, ``v [H, L, dv]``, head-major) beside the one
rotated ``k_pe [L, rope]`` all heads share::

    score_h(t, k) = scale * (q_nope_h(t) . k_nope_h(k) + q_pe_h(t) . k_pe(k)),  k <= start + t

Written in XLA, a chunk materialises each query tile's ``[H, tile, L]``
float32 scores in HBM, reads them back for every pass of the softmax and
re-reads the table's keys and values for every tile. This kernel keeps a
``[block_q, block_k]`` score tile in VMEM under the flash online softmax
(``ops/pallas/flash_attention.py``'s accumulator ``(acc, m, l)``), reads
each key block once a query block, and neither reads nor computes the
blocks wholly after a query block's last position: ``start`` is a
prefetched scalar, so the index maps clamp the key axis to the last block
the query block can see (Mosaic skips the copy of a repeated block index)
and ``pl.when`` skips the matmuls. Blocks wholly at or before every query
of the block take no mask.

Numerics: scores and the softmax in float32, the probabilities cast to the
values' dtype for the weighted sum, accumulated in float32, as
``ops.latent_attention.expanded_attention`` does (it normalises before the
sum; this kernel after).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning_mpi_tpu.ops.attention import NEG_INF
from deeplearning_mpi_tpu.runtime.compat import tpu_compiler_params

#: queries and keys a tile; the chunk and the table are cut to divisors
BLOCK_Q, BLOCK_K = 512, 512

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _kernel(
    start_ref, qn_ref, qp_ref, kn_ref, v_ref, kp_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int,
):
    i, j = pl.program_id(1), pl.program_id(2)
    q_first = start_ref[0] + i * block_q  # the block's first query position
    k_first = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool) -> None:
        s = (
            lax.dot_general(qn_ref[0], kn_ref[0], _NT, preferred_element_type=jnp.float32)
            + lax.dot_general(qp_ref[0], kp_ref[...], _NT, preferred_element_type=jnp.float32)
        ) * scale  # [bq, bk]
        if masked:
            q_pos = q_first + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = k_first + lax.broadcasted_iota(jnp.int32, s.shape, 1) <= q_pos
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # a row with every key so far masked has m_new == NEG_INF and exp(0) = 1
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], _NN, preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)

    # every key of the block at or before the block's first query: no mask
    pl.when(k_first + block_k - 1 <= q_first)(lambda: update(False))
    # the diagonal: some key after some query, none after the last
    pl.when(
        (k_first + block_k - 1 > q_first) & (k_first <= q_first + block_q - 1)
    )(lambda: update(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # every query sees key 0, so no row's sum is zero
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def chunk_attention(
    q_nope: jax.Array, q_pe: jax.Array, k_nope: jax.Array, v: jax.Array,
    k_pe: jax.Array, *, scale: float, start: jax.Array,
    block_q: int = BLOCK_Q, block_k: int = BLOCK_K, interpret: bool | None = None,
) -> jax.Array:
    """Causal attention of ``q_nope [C, H, nope]`` and ``q_pe [C, H, rope]``
    (rotated), query ``t`` at table position ``start + t``, over ``k_nope
    [H, L, nope]``, ``v [H, L, dv]`` and ``k_pe [L, rope]``. ``start`` is a
    traced int32 scalar. -> ``[C, H, dv]`` in ``q_nope``'s dtype.
    ``interpret=None``: compiled Mosaic on a TPU, the Pallas interpreter
    elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    chunk, heads, _ = q_nope.shape
    length, dv = k_pe.shape[0], v.shape[-1]
    bq, bk = math.gcd(chunk, block_q), math.gcd(length, block_k)
    n_k = length // bk

    def q_map(h, i, j, start_ref):
        return (h, i, 0)

    def last(i, start_ref):
        """The last key block query block ``i`` sees."""
        return jnp.minimum((start_ref[0] + (i + 1) * bq - 1) // bk, n_k - 1)

    def head_map(h, i, j, start_ref):
        return (h, jnp.minimum(j, last(i, start_ref)), 0)

    def shared_map(h, i, j, start_ref):
        return (jnp.minimum(j, last(i, start_ref)), 0)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    heads_first = lambda a: a.transpose(1, 0, 2)  # noqa: E731  [C, H, D] <-> [H, C, D]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=bq, block_k=bk),
        out_shape=jax.ShapeDtypeStruct((heads, chunk, dv), q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, chunk // bq, n_k),
            in_specs=[
                spec((1, bq, q_nope.shape[-1]), q_map),
                spec((1, bq, q_pe.shape[-1]), q_map),
                spec((1, bk, k_nope.shape[-1]), head_map),
                spec((1, bk, dv), head_map),
                spec((bk, k_pe.shape[-1]), shared_map),
            ],
            out_specs=spec((1, bq, dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),  # acc
                pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-replicated)
                pltpu.VMEM((bq, 128), jnp.float32),  # running sum
            ],
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.reshape(start, (1,)).astype(jnp.int32),
        heads_first(q_nope), heads_first(q_pe), k_nope, v, k_pe,
    )
    return heads_first(out)
