"""Absorbed latent attention for a decode step: a Pallas TPU kernel that walks
each row's live latent pages through its block table.

One query a row, ``W_uk`` already absorbed into it (``q_lat [rows, H,
kv_rank]``, beside the rotated ``q_pe [rows, H, rope]``), attends the
latent positions ``0 .. last[r]`` of row ``r`` in place, in the paged pools
``c [layers, blocks, BS, kv_rank]`` and ``k_pe [layers, blocks, rope, BS]``
(a page of ``k_pe`` holds its positions minor, as the TPU lays out a page of
64 values a position: 128 positions fill the lanes of its tiles)::

    score_h(t) = scale * (q_lat_h . c(t) + q_pe_h . k_pe(t))
    o_lat_h    = sum_t softmax_t(score_h)(t) c(t)

Written in XLA, a decode step gathers the table's whole rectangle of pages
(``[rows, width x BS, kv_rank]``: every row as wide as the widest) into HBM,
recomputes the gather for the weighted sum, and reads it once for the
scores and once for the sum. This kernel runs a grid over the rows; the
row's ``last`` position, the layer and the block tables are prefetched
scalars, so a row walks only the pages that hold its live positions (a
``lax.fori_loop`` whose trip count is set by ``last``), ``pages`` at a
time, copied straight from the pools in HBM by DMA into two VMEM buffers
(the next group's copy runs while this one's products do). Each page of
``c`` is read once, for the scores and for the weighted sum; the flash
online softmax (``acc [H, kv_rank]`` f32, running max and sum) stays in
VMEM. An inactive row (``last`` -1) walks nothing and yields zeros.

Numerics: scores and the softmax in float32, the probabilities cast to the
latent's dtype for the weighted sum, accumulated in float32, as
``ops.latent_attention.absorbed_attention`` does (it normalises before the
sum; this kernel after).

:func:`latent_decode` is ONE ``jax.jit``-wrapped function: a decode program
calls it at every latent layer with the same shapes (the layer is an
argument, not a constant), so JAX traces and lowers the kernel once per
program and reuses it at every layer's call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning_mpi_tpu.ops.attention import NEG_INF
from deeplearning_mpi_tpu.runtime.compat import tpu_compiler_params

#: latent positions a step of the page walk copies and attends: whole pages,
#: at most :data:`MAX_PAGES` of them (their copies are issued one by one)
GROUP_POSITIONS = 1024
MAX_PAGES = 16
#: the kernel's name, which its custom calls carry in the compiled program
#: and in the profiler's trace (``latent_decode.N``)
NAME = "latent_decode"

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel(
    layer_ref, last_ref, tables_ref,  # prefetched scalars
    q_lat_ref, q_pe_ref, c_hbm, kpe_hbm,  # [1, H, kv_rank], [1, H, rope]; the pools
    o_ref,  # [1, H, kv_rank]
    c_buf, kpe_buf, sem, acc_ref, m_ref, l_ref,
    *, scale: float, pages: int, width: int,
):
    row, layer = pl.program_id(0), layer_ref[0]
    last = last_ref[row]
    block = kpe_hbm.shape[-1]
    group = pages * block
    n_pages = (last + block) // block  # pages holding positions 0..last; 0 for an inactive row
    n_groups = (n_pages + pages - 1) // pages

    def copy(g, k, slot, start: bool) -> None:
        """Start (or wait for) the DMAs of page ``k`` of group ``g`` into
        buffer ``slot``; a page past the row's last live one is neither
        read nor waited for (its positions are masked below)."""
        page = g * pages + k

        @pl.when(page < n_pages)
        def _():
            blk = tables_ref[row * width + page]
            at = pl.ds(k * block, block)
            for dma in (
                pltpu.make_async_copy(c_hbm.at[layer, blk], c_buf.at[slot, at], sem.at[0, slot]),
                pltpu.make_async_copy(kpe_hbm.at[layer, blk], kpe_buf.at[slot, :, at], sem.at[1, slot]),
            ):
                if start:
                    dma.start()
                else:
                    dma.wait()

    @pl.when(row == 0)
    def _zero():
        # a buffer slot a row's last group leaves unread is masked, and a
        # masked position's weight is 0: it must hold a finite value
        c_buf[...] = jnp.zeros_like(c_buf)
        kpe_buf[...] = jnp.zeros_like(kpe_buf)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    for k in range(pages):
        copy(0, k, 0, start=True)

    q_lat, q_pe = q_lat_ref[0].astype(c_buf.dtype), q_pe_ref[0]

    def step(g, carry):
        slot = g % 2

        for k in range(pages):
            copy(g + 1, k, 1 - slot, start=True)
        for k in range(pages):
            copy(g, k, slot, start=False)
        c, k_pe = c_buf[slot], kpe_buf[slot]
        s = (
            lax.dot_general(q_lat, c, _NT, preferred_element_type=jnp.float32)
            + lax.dot_general(q_pe, k_pe, _NN, preferred_element_type=jnp.float32)
        ) * scale  # [H, group]
        pos = g * group + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= last, s, NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # position 0 is in group 0, so m_new is a live score's
        alpha = jnp.exp(m_prev - m_new)
        pv = lax.dot_general(p.astype(c.dtype), c, _NN, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        return carry

    lax.fori_loop(0, n_groups, step, 0)
    l = l_ref[:, :1]
    o_ref[0] = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "group_positions", "interpret"))
def latent_decode(
    q_lat: jax.Array, q_pe: jax.Array, c_pool: jax.Array, kpe_pool: jax.Array,
    layer: jax.Array, tables: jax.Array, last: jax.Array, *, scale: float,
    group_positions: int = GROUP_POSITIONS, interpret: bool = False,
) -> jax.Array:
    """Attention of ``q_lat [rows, H, kv_rank]`` and ``q_pe [rows, H,
    rope]`` over row ``r``'s latent positions ``0 .. last[r]``, page ``i``
    of which is block ``tables[r, i]`` of layer ``layer`` of the pools
    ``c_pool [layers, blocks, BS, kv_rank]`` and ``kpe_pool [layers, blocks,
    rope, BS]``. ``q_lat`` may be wider than the pools' dtype (the absorbed
    query as its product left it): the kernel casts it. ``layer`` is a
    traced int32 scalar, ``last [rows]`` int32 (-1: an inactive row, zeros
    out). -> ``o_lat [rows, H, kv_rank]`` in ``q_pe``'s dtype.
    ``interpret``: the Pallas interpreter (a machine without a TPU)."""
    rows, heads, kv_rank = q_lat.shape
    rope, block = kpe_pool.shape[2:]
    width = tables.shape[1]
    # the groups do not depend on the table's width: a row's walk, and so
    # its output, is the same in any table that holds its pages
    pages = max(1, min(group_positions // block, MAX_PAGES))
    group = pages * block

    def one_row(r, *_):
        return (r, 0, 0)

    def spec(block_shape):
        return pl.BlockSpec(block_shape, one_row, memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, pages=pages, width=width),
        out_shape=jax.ShapeDtypeStruct((rows, heads, kv_rank), q_pe.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows,),
            in_specs=[
                spec((1, heads, kv_rank)),
                spec((1, heads, rope)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=spec((1, heads, kv_rank)),
            scratch_shapes=[
                pltpu.VMEM((2, group, kv_rank), c_pool.dtype),
                pltpu.VMEM((2, rope, group), kpe_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, kv_rank), jnp.float32),  # acc
                pltpu.VMEM((heads, 128), jnp.float32),  # running max (lane-replicated)
                pltpu.VMEM((heads, 128), jnp.float32),  # running sum
            ],
        ),
        # in order: the first row zeroes the buffers every later row reuses
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), last.astype(jnp.int32),
        tables.reshape(-1).astype(jnp.int32), q_lat, q_pe, c_pool, kpe_pool,
    )
