"""Serving engine: fixed-shape jitted steps, host-swapped sequences.

The offline path (``models/generate``) compiles one program per batch whose
cache is sized ``prompt + max_new`` and whose rows march in lockstep. A
serving engine inverts every one of those assumptions: requests arrive and
finish independently, so the engine compiles a small fixed set of programs
once — a batched decode step over up to ``max_slots`` rows, a per-slot
prefill chunk, and (with speculative decoding on) a batched multi-token
verify step — and a host-side loop swaps finished sequences out of slots
between steps. Every jitted shape is static and comes from a short list
warmed before traffic (the block table's rows x width bucket, chunk width,
speculation width: ``_table_shapes``), so admission, completion, and
eviction never trigger recompilation; what changes step to step is which
warmed shape the live sequences pick and the *contents* of the arrays (block
tables, fill levels, last tokens, active mask).

Layer map (see ``docs/SERVING.md`` for the full walkthrough):

- :mod:`~deeplearning_mpi_tpu.serving.kv_pool` owns block accounting and
  the ``[num_layers, num_blocks, block_size, Hkv, D]`` device pools (and
  the ``[..., block_size, Di]`` indexer-key pool of a selecting model);
- :mod:`~deeplearning_mpi_tpu.serving.scheduler` owns policy (admission,
  deadlines, oldest-first eviction under KV pressure, bucketed decode-batch
  formation);
- :mod:`~deeplearning_mpi_tpu.serving.speculative` owns the draft model:
  its own (smaller) KV pools written through the SAME block tables, so one
  allocation serves both models;
- this module owns target-model compute, factored into
  :class:`PagedForward` so the draft model reuses the identical programs at
  its own dimensions. The decode step scatters each slot's new K/V through
  its block table (inactive slots write to the scratch block), gathers each
  slot's pages back into a ``[S, L, Hkv, D]`` view, and runs
  :func:`~deeplearning_mpi_tpu.ops.attention.batched_decode_attention`,
  the per-row masked einsum. Prefill is
  chunked: each PREFILL slot advances one ``prefill_chunk``-wide causal
  forward per engine step, so a long prompt cannot stall decode for every
  other slot. The verify step is a width-``spec_k + 1`` extension of the
  prefill chunk, batched over slots with PER-ROW query offsets: row ``s``
  feeds its last known token plus ``spec_k`` draft proposals at absolute
  positions ``lengths[s]-1 ..``, and the returned argmaxes are the target
  model's greedy continuation at every one of those positions — accepting
  the longest proposal prefix that matches them is what keeps speculative
  output bit-identical to offline greedy decode regardless of draft
  quality.

The forward mirrors ``models.transformer.TransformerLM`` numerics exactly
(dtype-cast matmuls on f32 params, f32 norm/softmax accumulation, tied or
untied head) but runs over the raw param tree rather than a flax apply:
the flax ``Attention`` cache carries ONE scalar ``cache_index`` for the
whole batch — the lockstep assumption this engine exists to break — so the
cached-attention module cannot express per-slot fill levels. Parity with
the offline path is pinned by ``tests/test_serving.py`` (greedy outputs
identical per request, speculative and plain).

Greedy-only. Two layer kinds beyond the dense block are written once, in
``PagedForward._layers``, and chosen by the model's config alone (a third
thing the config alone decides: a model whose layers differ in their sliding
window, ``TransformerConfig.layers``, keeps its full and its window layers in
two GROUPS, each with its own pools, allocator and block table:
:func:`layer_groups`):

- learned sparse attention (``attention_topk > 0``): a third pool holds one
  indexer key a position beside K and V; a query scores the table's indexer
  keys, keeps the ``attention_topk`` highest and gathers only those K/V rows
  through the block table (``ops/sparse_attention.py``), in the decode step
  and in the prefill chunk alike. The verify step does not select, and the
  indexer pool has no integer storage: ``spec_k > 0`` and an integer
  ``kv_dtype`` are refused for such a model;
- a dropless expert layer (``moe_routing='dropless'``,
  ``models/moe.py:dropless_moe``): every claim is served, so a token's
  output depends on the token alone. CAPACITY routing stays refused: there a
  token's output depends on which OTHER tokens share its batch (capacity
  contention), which would break the engine's request-independence contract
  — co-batched strangers must never change your completion. The layer has
  two forms that round differently, picked by the program's static rows
  (``dropless_form``); the engine builds only the decode row buckets that
  take the form of its ``max_slots``-row program, so the contract holds
  across buckets too. Leading dense layers, a shared expert, sigmoid
  routing and a SHARE of the layer's experts (the router scores all of
  them, this engine computes the part its own give) are the config's too;
- multi-head latent attention (``kv_lora_rank > 0``, DeepSeek-V3's MLA):
  the pool holds ONE latent vector a position a layer, ``[c ; RoPE(k_pe)]``,
  in two arrays where K and V would be, and no V pool
  (``init_kv_buffers(latent_dims=...)``). The decode step attends in the
  *absorbed* form (the key projection moved onto the query, the value
  projection past the weighted sum: the latent is read once for every
  head) in a Pallas kernel that walks each row's live pages in the pools
  through its block table, so its table has one width (the full one) at
  every row bucket; the prefill chunk in the *expanded* form (every table
  position's keys and values expanded once for the chunk's queries, which
  attend in a Pallas kernel that keeps their scores on chip):
  ``ops/latent_attention.py``. Speculation, integer pools and a
  disaggregated hand-off are refused for such a model.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_mpi_tpu.runtime.compat import buffer_donation_supported
from deeplearning_mpi_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    rope_kwargs,
)
from deeplearning_mpi_tpu.ops.attention import (
    NEG_INF,
    batched_decode_attention,
    dense_attention,
    repeat_kv,
)
from deeplearning_mpi_tpu.analysis import sanitizer as _sanitizer
from deeplearning_mpi_tpu.models.moe import (
    dropless_form,
    dropless_moe,
    routing_from_config,
)
from deeplearning_mpi_tpu.ops.latent_attention import (
    chunk_attention,
    paged_absorbed_attention,
)
from deeplearning_mpi_tpu.ops.pallas import latent_decode
from deeplearning_mpi_tpu.ops.quant import dequantize_kv, quantize_kv
from deeplearning_mpi_tpu.ops.sparse_attention import (
    attend_masked,
    attend_selected,
    indexer_scores,
    select_mask,
    select_topk,
)
from deeplearning_mpi_tpu.serving.launch import dispatch, fetch, h2d
from deeplearning_mpi_tpu.serving.kv_pool import (
    SCRATCH_BLOCK,
    PagedKVPool,
    init_kv_buffers,
)
from deeplearning_mpi_tpu.serving.scheduler import (
    Request,
    RequestState,
    Scheduler,
)
from deeplearning_mpi_tpu.telemetry.trace import annotate, span

__all__ = [
    "EngineConfig", "KVBuffers", "LayerGroup", "PagedForward", "ServingEngine",
    "layer_groups",
]

#: Queries that attend a long table at a time: a selecting layer's, and the
#: full layers' of a model with window and full layers. A prefill chunk's
#: queries go through in tiles of this many: per tile the float32
#: indexer products are ``[tile, Hi, L]`` and the attention scores
#: ``[H, tile, L]`` (at 16 and 32 heads and L = 65,536: 0.27 GB and 0.54
#: GB), where the whole chunk at once would not fit beside the weights.
SELECT_TILE = 64


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """Layers whose cached state is of one kind: they share ``(k, v)`` pools
    of ``[layers in group, blocks of group, block_size, Hkv, D]``, one
    allocator, one block list a request and one table on the device."""

    #: the layers' sliding window (0: every earlier key, whole tables)
    window: int
    #: the model's layer numbers in this group, in order: layer
    #: ``layers[j]`` lives at index ``j`` of the group's pools
    layers: tuple[int, ...]


def layer_groups(config: TransformerConfig) -> tuple[LayerGroup, ...]:
    """The model's layers by kind of cached state. One window for every
    layer (or none) is ONE group, the engine of a one-kind model. Full and
    window layers side by side are two: the full group first, then the
    window group, whose tables start at :func:`window_first_block` and whose
    blocks behind it go back to its pool."""
    windows = [config.layer_spec(i).window for i in range(config.num_layers)]
    kinds = sorted(set(windows))
    if len(kinds) > 2 or (len(kinds) == 2 and kinds[0] != 0):
        raise NotImplementedError(
            f"layers of sliding windows {kinds}: the serving engine keeps "
            "one group of full layers and one of window layers (window "
            "groups of several sizes are not implemented)"
        )
    return tuple(
        LayerGroup(w, tuple(i for i, lw in enumerate(windows) if lw == w))
        for w in kinds
    )


def _by_query_tiles(
    attend_tile: Callable[[tuple[jax.Array, ...]], jax.Array],
    per_query: tuple[jax.Array, ...],
) -> jax.Array:
    """``attend_tile`` over tiles of :data:`SELECT_TILE` queries, one tile
    at a time: ``per_query`` are ``[rows, seq, ...]`` arrays, the first the
    queries ``[rows, seq, H, D]``; the result is ``[rows, seq, H, Dv]``."""
    q = per_query[0]
    rows, seq = q.shape[:2]
    width = math.gcd(seq, SELECT_TILE)
    if width == seq:
        return attend_tile(per_query)
    # [rows, seq, ...] -> [tiles, rows, width, ...], one tile at a time
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((rows, seq // width, width) + a.shape[2:]), 1, 0
    )
    out = jax.lax.map(attend_tile, tuple(map(split, per_query)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[:2] + out.shape[3:])


class GroupView(NamedTuple):
    """What one program hands :meth:`PagedForward._layers` for one group of
    layers: where this step's K/V rows go, the group's table, and the
    attention over its gathered pages."""

    bid: jax.Array     # block id each new K/V row is written to
    off: jax.Array     # offset in that block; same shape as ``bid``
    tables: jax.Array  # [rows, MB] block ids, or [MB] for one row
    attend: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def window_first_block(length: Any, window: int, block_size: int) -> Any:
    """Index, within a sequence's block table, of the first block a query
    at the last of ``length`` known positions can reach under a sliding
    window of ``window`` positions: the query sits at ``length - 1`` and
    sees ``length - window .. length - 1``, so every block before
    ``max(length - window, 0) // block_size`` is out of reach.

    THE one place the formula lives. ``ServingEngine._plain_decode`` cuts a
    row's table here (``length`` a host int or numpy array) and
    ``PagedForward.decode_step`` shifts its block index and its attention
    index by it (``length`` a traced array): operators only, so both get
    their own kind back and the two halves cannot drift."""
    past = length - window  # positions the window has left behind
    return past * (past > 0) // block_size


def window_blocks(window: int, block_size: int) -> int:
    """The most blocks a row's table can hold from
    :func:`window_first_block` on: ``window`` positions that start
    anywhere in a block."""
    return -(-window // block_size) + 1


def _table_shapes(
    max_slots: int, max_blocks: int, decode_blocks: int | None = None
) -> tuple[tuple[int, ...], tuple[tuple[int, int], ...]]:
    """The static shapes a block table may take, from the engine's
    ceilings alone: the ladder of widths (the prefill chunk's; the verify
    step's and the draft's at ``max_slots`` rows) and the decode step's
    (rows, width) pairs. Together they are the programs ``warmup()`` pays
    for, so both lists are short.

    Widths: the powers of two from an eighth of the full table up, then the
    full table; a rung below an eighth would save under a sixteenth of the
    full gather. Decode pairs: the same ladder built on ``decode_blocks``,
    the most blocks a decode row can hand over (``max_blocks`` unless a
    sliding window caps it lower: :func:`window_blocks`); a quarter, a half
    and all of ``max_slots`` rows at its two widest rungs, and ``max_slots``
    rows at every rung. The gather costs rows x width, so a narrow table is
    cheap already and a rung saves the most where the rows are many: the
    narrow rungs are not multiplied by the row buckets."""

    def ladder(top: int) -> list[int]:
        rungs, w = [], 1
        while w < top:
            if 8 * w >= top:
                rungs.append(w)
            w *= 2
        return rungs + [top]

    decode = ladder(decode_blocks or max_blocks)
    rows = {-(-max_slots // 4), -(-max_slots // 2), max_slots}
    pairs = {(r, w) for r in rows for w in decode[-2:]}
    pairs |= {(max_slots, w) for w in decode}
    return tuple(ladder(max_blocks)), tuple(sorted(pairs))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shape/policy knobs — all of them baked into the compiled
    programs, none of them changeable without a (deliberate) recompile."""

    #: decode rows per jitted step; also the number of concurrent sequences
    max_slots: int = 4
    #: token positions per KV block
    block_size: int = 16
    #: pool blocks per layer, scratch block included (of a model with full
    #: and window layers: the full group's)
    num_blocks: int = 64
    #: the window group's pool blocks per layer, scratch block included: a
    #: model with full and window layers side by side (:func:`layer_groups`)
    #: keeps the window layers' K/V in a pool of their own, sized for the
    #: window and not for the sequence. 0 for every other model.
    window_num_blocks: int = 0
    #: block-table width = admission ceiling: a sequence may span at most
    #: ``max_blocks_per_seq * block_size`` positions (prompt + generation)
    max_blocks_per_seq: int = 8
    #: prompt positions prefilled per slot per engine step
    prefill_chunk: int = 16
    #: bounded request queue (admission control)
    max_queue: int = 64
    #: draft proposals verified per sequence per engine step (0 = plain
    #: decode). With ``spec_k > 0`` the engine needs a draft model
    #: (``ServingEngine(draft_config=..., draft_params=...)``) and every
    #: decode iteration becomes one draft propose loop + ONE jitted verify
    #: step emitting up to ``spec_k + 1`` tokens per sequence.
    spec_k: int = 0
    #: decode-batch formation buckets (ascending, e.g. ``(8, 16, 32)``):
    #: the scheduler HOLDS the decode phase for up to ``max_hold_steps``
    #: engine steps while queued/prefilling supply could still grow the
    #: decode batch toward the next bucket — so batches of 8-32 actually
    #: form under load instead of trickling in at 1-4. Empty = decode
    #: every step (the pre-bucketing behavior). Holding only delays
    #: decode, so completions stay bit-identical.
    decode_buckets: tuple[int, ...] = ()
    #: hold budget (engine steps) for decode-batch formation; the budget
    #: resets every time a decode step actually runs, so decode is never
    #: deferred more than this many consecutive steps
    max_hold_steps: int = 4
    #: KV-cache storage dtype, by NAME so the config stays JSON-round-
    #: trippable across the fleet's spec files. ``None`` = the engine's
    #: compute dtype (the default — keeps the bit-identical-to-offline-
    #: greedy invariant untouched). ``"int8"`` stores quantized pages plus
    #: per-token-row f32 scales (``ops/quant.quantize_kv``), dequantized
    #: inside the jitted gather — an opt-in capacity multiplier whose
    #: output is tolerance-gated, not bit-exact (docs/SERVING.md).
    kv_dtype: str | None = None
    #: radix prefix cache (``serving/prefix_cache.py``): completed prompt
    #: prefixes are indexed by token span and later requests with the same
    #: prefix adopt the KV blocks (refcounted, copy-on-write) instead of
    #: re-prefilling them. Off by default — the cacheless path stays
    #: byte-identical to the pre-cache engine; with it on, streams are
    #: still bit-identical to offline greedy (docs/SERVING.md "Prefix
    #: cache & multi-tenancy").
    prefix_cache: bool = False

    @property
    def max_seq_len(self) -> int:
        return self.max_blocks_per_seq * self.block_size

    @property
    def kv_quantized(self) -> bool:
        """True when the KV pools store an integer dtype (scales ride
        alongside and the gather dequantizes)."""
        if self.kv_dtype is None:
            return False
        return jnp.issubdtype(jnp.dtype(self.kv_dtype), jnp.integer)


class KVBuffers:
    """Mutable holder for the device KV pools a :class:`ServingEngine`
    threads through its jitted steps — ``(k, v)`` for float storage,
    ``(k, v, k_scale, v_scale)`` for quantized storage, ``(k, v, k_index)``
    for a model with learned sparse attention (see
    :func:`~deeplearning_mpi_tpu.serving.kv_pool.init_kv_buffers`); for a
    model with full and window layers one such tuple a group
    (:func:`layer_groups`), ``((k, v), (k, v))``.

    The indirection exists for disaggregation: a prefill-only and a
    decode-only engine share ONE set of pools (handoff transfers block-
    table ownership, never copies pages), and because every step donates
    and rebinds the buffers, the shared thing must be this holder, not the
    arrays — whichever engine stepped last leaves the live buffers here
    for the other to pick up.
    """

    __slots__ = ("bufs",)

    def __init__(self, bufs: tuple[Any, ...]) -> None:
        self.bufs = bufs

    @property
    def nbytes(self) -> int:
        return sum(int(b.nbytes) for b in jax.tree.leaves(self.bufs))


class PagedForward:
    """``TransformerLM`` numerics over paged KV block tables.

    One instance per model: the engine builds one for the target and
    ``serving.speculative.SpeculativeDecoder`` builds one for the draft —
    same programs, same block geometry (``engine.block_size`` /
    ``max_blocks_per_seq``), different model dims and KV pools. ``tick``
    is called at TRACE time of every program (the engine wires it to the
    ``serve_compile_total`` counter so "zero compiles on the first
    request" stays an assertable counter delta).

    ``kv_dtype`` (a dtype, or None for full precision) selects the KV
    storage format. Every program threads one ``kv`` tuple — ``(k, v)``
    pools, plus ``(k_scale, v_scale)`` when quantized — and all scatter/
    gather goes through :meth:`_kv_scatter` / :meth:`_kv_gather`, so the
    int8 path quantizes rows on the way into the pool and dequantizes
    inside the gather, leaving the attention math itself dtype-blind.
    Both index the whole ``[layers, blocks, ...]`` pool with the layer in
    the index: no program holds a value of one layer's pool shape
    (``tests/test_serving.py::test_page_gather_reads_the_pool_in_place``).
    """

    def __init__(
        self,
        config: TransformerConfig,
        engine: EngineConfig,
        dtype: Any,
        *,
        tick: Callable[[], None] | None = None,
        kv_dtype: Any = None,
        window_cut: bool = False,
    ) -> None:
        self.config = config
        self.engine = engine
        self.dtype = dtype
        self.kv_dtype = kv_dtype
        #: the layers by kind of cached state (:func:`layer_groups`). One
        #: group: every program takes ONE table and a flat ``kv`` tuple. Two
        #: (``mixed``): a table a group, ``(full, window)``, and ``kv`` a
        #: tuple of the groups' tuples.
        self.groups = layer_groups(config)
        self.mixed = len(self.groups) > 1
        #: layer -> (its group, its index in the group's pools)
        self._place = {
            layer: (g, j)
            for g, group in enumerate(self.groups)
            for j, layer in enumerate(group.layers)
        }
        #: each layer's own RoPE (``apply_rope``'s keywords)
        self._rope_kw = [
            rope_kwargs(config.rope_dim, spec.rope_theta, spec.yarn)
            for spec in map(config.layer_spec, range(config.num_layers))
        ]
        #: multi-head latent attention's shapes (None: K/V attention): the
        #: kv tuple is the latent pool ``(c, k_pe)``, scattered and gathered
        #: where K and V would be
        self.latent = config.latent
        #: the sliding window, where the caller of :meth:`decode_step` hands
        #: it tables that start at :func:`window_first_block`
        #: (``window_cut``: the engine's own decode launch does, the draft's
        #: propose loop hands whole tables; the window group of a mixed
        #: model always) AND a sequence can outgrow the window; else 0, and
        #: the decode program is the one of a model without a window
        window = self.groups[-1].window
        self.decode_window = (
            window
            if (window_cut or self.mixed) and 0 < window < engine.max_seq_len
            else 0
        )
        self.quantized = kv_dtype is not None and jnp.issubdtype(
            jnp.dtype(kv_dtype), jnp.integer
        )
        #: learned sparse attention: the kv tuple carries a third pool, the
        #: indexer keys, and wide tables go through the selecting layer
        self.selecting = config.attention_topk > 0
        self._tick = tick or (lambda: None)

    # -- paged scatter/gather (the storage-format seam) ----------------------
    def _kv_scatter(
        self,
        kv: tuple[jax.Array, ...],
        i: int,
        bid: jax.Array,
        off: jax.Array,
        k: jax.Array,
        v: jax.Array,
        k_idx: jax.Array | None = None,
    ) -> tuple[jax.Array, ...]:
        """Write this step's new K/V rows (``[..., Hkv, D]``) through the
        block table at layer ``i``; a selecting model's indexer keys
        (``k_idx [..., Di]``) go to the third pool at the same places.
        Quantized storage also writes the per-row scales — data and scales
        land in ONE jitted program, which is what makes the pool's
        scale/block epoch check a real invariant rather than a race
        window.

        A latent model's pool takes ``c`` at K's place and ``k_pe`` at V's,
        whose block holds its positions minor (``init_kv_buffers``)."""
        with annotate("attn/kv_scatter"):
            if self.latent:
                c_pool, kpe_pool = kv
                return (
                    c_pool.at[i, bid, off].set(k.astype(c_pool.dtype)),
                    kpe_pool.at[i, bid, :, off].set(v.astype(kpe_pool.dtype)),
                )
            if not self.quantized:
                k_pool, v_pool, *index = kv
                out = (
                    k_pool.at[i, bid, off].set(k.astype(k_pool.dtype)),
                    v_pool.at[i, bid, off].set(v.astype(v_pool.dtype)),
                )
                if index:
                    (i_pool,) = index
                    out += (i_pool.at[i, bid, off].set(k_idx.astype(i_pool.dtype)),)
                return out
            k_pool, v_pool, k_scale, v_scale = kv
            qk, sk = quantize_kv(k)
            qv, sv = quantize_kv(v)
            return (
                k_pool.at[i, bid, off].set(qk),
                v_pool.at[i, bid, off].set(qv),
                k_scale.at[i, bid, off].set(sk),
                v_scale.at[i, bid, off].set(sv),
            )

    def _kv_gather(
        self, kv: tuple[jax.Array, ...], i: int, tables: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """Gather layer ``i``'s pages through ``tables``, returning K/V in
        the compute dtype — the int8 path dequantizes here, inside the
        jitted program, so downstream attention never sees storage.

        The pool is read in place: ``pool[i, tables]`` is ONE gather over
        the whole ``[layers, blocks, ...]`` buffer. ``pool[i][tables]``
        first slices out layer ``i``, and XLA materialises the slice — a
        copy of a layer's whole pool per K and V per layer in every
        program, 13 ms of a 37 ms decode step on the v5e (PERF.md, PR 28)."""
        with annotate("attn/kv_gather"):
            if self.latent:
                # (c, k_pe) pages, k_pe's positions brought back second-minor
                c_pool, kpe_pool = kv
                return c_pool[i, tables], jnp.swapaxes(kpe_pool[i, tables], -1, -2)
            if not self.quantized:
                k_pool, v_pool = kv[:2]
                return k_pool[i, tables], v_pool[i, tables]
            k_pool, v_pool, k_scale, v_scale = kv
            return (
                dequantize_kv(k_pool[i, tables], k_scale[i, tables], self.dtype),
                dequantize_kv(v_pool[i, tables], v_scale[i, tables], self.dtype),
            )

    # -- copy-on-write block copy (prefix cache) -----------------------------
    def copy_block(
        self, kv: tuple[jax.Array, ...], src: jax.Array, dst: jax.Array
    ) -> tuple[jax.Array, ...]:
        """Copy every pool's pages for block ``src`` into block ``dst``
        (all layers, data AND scales — or indexer keys — in one program:
        same atomicity argument as :meth:`_kv_scatter`). The prefix cache's CoW step: an
        adopter of a partially-matched shared block gets a private copy to
        write its divergent tail into. ``src``/``dst`` are traced scalars,
        so one compilation covers every copy."""
        self._tick()
        return tuple(buf.at[:, dst].set(buf[:, src]) for buf in kv)

    # -- building blocks (mirror TransformerLM numerics) ---------------------
    # Each block opens its own scope (``embed``, ``attn/qkv``, ``attn/out``,
    # ``mlp``, ``logits``; ``attn/kv_scatter``/``attn/kv_gather`` above and
    # ``attn/core`` in ``_layers``), so the three programs' HLO ``op_name``s
    # say which part of a layer an operation belongs to.
    def _lin(self, x: jax.Array, kernel: jax.Array) -> jax.Array:
        # flax nn.Dense(use_bias=False, dtype=d): both operands cast to the
        # compute dtype, f32 params untouched in the tree.
        return x.astype(self.dtype) @ kernel.astype(self.dtype)

    def _rmsnorm(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.config.rms_norm_eps
        )
        return (normed * scale).astype(x.dtype)

    def _embed(self, params: Any, tokens: jax.Array) -> jax.Array:
        with annotate("embed"):
            return params["embed"]["embedding"].astype(self.dtype)[tokens]

    def _logits(self, x: jax.Array, params: Any) -> jax.Array:
        with annotate("logits"):
            if self.config.tied_embeddings:
                emb = params["embed"]["embedding"]
                return (
                    x.astype(self.dtype) @ emb.astype(self.dtype).T
                ).astype(jnp.float32)
            return self._lin(x, params["lm_head"]["kernel"]).astype(
                jnp.float32
            )

    def _attn_proj(
        self, lp: Any, x: jax.Array, pos: jax.Array, i: int
    ) -> tuple[jax.Array, jax.Array, jax.Array, tuple[jax.Array, ...] | None]:
        """Pre-attention norm, Q/K/V projections (per-head RMSNorm where the
        model has it) and RoPE of layer ``i`` (its own base and scaling); for
        a selecting model also the indexer's projections
        (``models.transformer.Indexer`` numerics)
        ``(qI [rows, seq, Hi, Di], w [rows, seq, Hi], kI [rows, seq, Di])``,
        else None."""
        cfg = self.config
        rows, seq = x.shape[0], x.shape[1]
        kv_heads = cfg.num_kv_heads or cfg.num_heads
        rope = functools.partial(apply_rope, positions=pos, **self._rope_kw[i])
        with annotate("attn/qkv"):
            h = self._rmsnorm(x, lp["attn_norm"]["scale"])
            q = self._lin(h, lp["attn"]["q_proj"]["kernel"]).reshape(
                rows, seq, cfg.num_heads, cfg.head_dim
            )
            k = self._lin(h, lp["attn"]["k_proj"]["kernel"]).reshape(
                rows, seq, kv_heads, cfg.head_dim
            )
            v = self._lin(h, lp["attn"]["v_proj"]["kernel"]).reshape(
                rows, seq, kv_heads, cfg.head_dim
            )
            if cfg.qk_norm:
                q = self._rmsnorm(q, lp["attn"]["q_norm"]["scale"])
                k = self._rmsnorm(k, lp["attn"]["k_norm"]["scale"])
            index = None
            if self.selecting:
                ip = lp["attn"]["indexer"]
                q_idx = self._lin(h, ip["q_proj"]["kernel"]).reshape(
                    rows, seq, cfg.indexer_heads, cfg.indexer_head_dim
                )
                k_idx = self._rmsnorm(
                    self._lin(h, ip["k_proj"]["kernel"]), ip["k_norm"]["scale"]
                )
                index = (
                    rope(q_idx),
                    self._lin(h, ip["w_proj"]["kernel"]),
                    rope(k_idx[:, :, None])[:, :, 0],
                )
            return rope(q), rope(k), v, index

    def _latent_proj(
        self, lp: Any, x: jax.Array, pos: jax.Array, i: int
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Latent attention's projections of layer ``i``
        (``models.transformer.LatentAttention`` numerics): ``(q_nope [rows,
        seq, H, nope], q_pe [rows, seq, H, rope] rotated, c [rows, seq,
        kv_rank] normed, k_pe [rows, seq, rope] rotated)``, the last two
        what the latent pool holds a position."""
        cfg, sp, at = self.config, self.latent, lp["attn"]
        rows, seq = x.shape[0], x.shape[1]
        rope = functools.partial(apply_rope, positions=pos, **self._rope_kw[i])
        with annotate("attn/latent_q"):
            h = self._rmsnorm(x, lp["attn_norm"]["scale"])
            q_a = self._rmsnorm(self._lin(h, at["q_a_proj"]["kernel"]), at["q_a_norm"]["scale"])
            q = self._lin(q_a, at["q_b_proj"]["kernel"]).reshape(
                rows, seq, cfg.num_heads, sp.nope + sp.rope
            )
            kv_a = self._lin(h, at["kv_a_proj"]["kernel"])
            c = self._rmsnorm(kv_a[..., : sp.kv_rank], at["kv_a_norm"]["scale"])
            k_pe = rope(kv_a[..., sp.kv_rank :][:, :, None])[:, :, 0]
            return q[..., : sp.nope], rope(q[..., sp.nope :]), c, k_pe

    def _attn_out(self, lp: Any, x: jax.Array, ctx: jax.Array) -> jax.Array:
        """Residual add of the attention output projection; ``ctx`` is
        ``[rows, seq, H, D]``."""
        with annotate("attn/out"):
            return x + self._lin(
                ctx.reshape(x.shape[0], x.shape[1], -1),
                lp["attn"]["out_proj"]["kernel"],
            )

    def _moe(
        self, lp: Any, x: jax.Array, live: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """The dropless expert layer (``models.moe.dropless_moe``) over
        this step's rows as one flat batch; padding rows (``live`` false)
        claim no expert. Returns the residual sum and the count of experts
        the live rows touched."""
        mp, cfg = lp["mlp"], self.config
        with annotate("mlp"):
            h = self._rmsnorm(x, lp["mlp_norm"]["scale"])
            y, touched = dropless_moe(
                h.reshape(-1, h.shape[-1]), mp["router"]["kernel"],
                mp["experts_gate"], mp["experts_up"], mp["experts_down"],
                top_k=cfg.moe_top_k, dtype=self.dtype,
                live=live.reshape(-1), routing=routing_from_config(cfg),
                bias=mp["router"].get("bias"), shared=mp.get("shared"),
                count_claims=cfg.expert_share,
            )
            return x + y.reshape(x.shape), touched

    def _mlp(self, lp: Any, x: jax.Array) -> jax.Array:
        with annotate("mlp"):
            h = self._rmsnorm(x, lp["mlp_norm"]["scale"])
            hidden = jax.nn.silu(
                self._lin(h, lp["mlp"]["gate_proj"]["kernel"])
            ) * self._lin(h, lp["mlp"]["up_proj"]["kernel"])
            return x + self._lin(hidden, lp["mlp"]["down_proj"]["kernel"])

    def _select_attend(
        self,
        kv: tuple[jax.Array, ...],
        i: int,
        tables: jax.Array,  # [rows, MB]
        q: jax.Array,       # [rows, seq, H, D]
        q_idx: jax.Array,   # [rows, seq, Hi, Di]
        w_idx: jax.Array,   # [rows, seq, Hi]
        q_pos: jax.Array,   # [rows, seq] absolute position, -1 = padding
    ) -> jax.Array:
        """Attention of a selecting layer over the paged pools: gather the
        table's INDEXER keys (the small pool), score every key for every
        query, keep the ``attention_topk`` highest at or before the query,
        attend those. Which K/V traffic that takes is read off the shapes:

        - few queries (a decode step: one a row): the kept positions
          (:func:`select_topk`) are gathered row by row through the block
          table (token ``t`` lives at ``tables[t // BS], t % BS``) — the
          table's K/V pages as a whole are never read;
        - ``seq * topk`` rows would be more than the table holds (a prefill
          chunk): the table's pages are gathered once for all the chunk's
          queries and each attends under its own mask
          (:func:`select_mask`).

        Queries go through in tiles of :data:`SELECT_TILE`. Padding queries
        see no key and yield zeros. -> ``[rows, seq, H, D]``."""
        cfg, BS = self.config, self.engine.block_size
        k_pool, v_pool, i_pool = kv
        rows, seq = q_pos.shape
        span = tables.shape[1] * BS
        with annotate("attn/kv_gather"):
            k_idx = i_pool[i, tables].reshape(rows, span, -1)
        pages = seq * cfg.attention_topk > span
        if pages:
            k_seq, v_seq = (
                a.reshape((rows, span) + a.shape[-2:])
                for a in self._kv_gather(kv, i, tables)
            )
        k_pos = jnp.arange(span, dtype=jnp.int32)
        row = jnp.arange(rows)[:, None, None]

        def attend_tile(tile: tuple[jax.Array, ...]) -> jax.Array:
            q_t, qi_t, w_t, pos_t = tile
            scores = indexer_scores(qi_t, w_t, k_idx)
            visible = k_pos[None, None, :] <= pos_t[:, :, None]
            if pages:
                mask = select_mask(scores, visible, cfg.attention_topk)
                with annotate("attn/core"):
                    return attend_masked(q_t, k_seq, v_seq, mask)
            ids, kept = select_topk(scores, visible, cfg.attention_topk)
            with annotate("attn/sparse_gather"):
                blk, off = tables[row, ids // BS], ids % BS
                k_sel, v_sel = k_pool[i, blk, off], v_pool[i, blk, off]
            with annotate("attn/core"):
                return attend_selected(q_t, k_sel, v_sel, kept)

        return _by_query_tiles(attend_tile, (q, q_idx, w_idx, q_pos))

    def _layers(
        self,
        params: Any,
        kv: tuple[Any, ...],
        x: jax.Array,       # [rows, seq, d] embedded tokens
        pos: jax.Array,     # [rows, seq] absolute positions (RoPE)
        views: list[GroupView],  # one a group of layers
    ) -> tuple[tuple[Any, ...], jax.Array, jax.Array]:
        """THE layer loop of all three programs: per layer, project, scatter
        the new K/V rows through its group's ``(bid, off)``, attend, output
        projection, MLP; then the final norm. A program is its index math
        for ``pos`` and each group's :class:`GroupView`, and its head; a
        layer kind is written here once. A layer reads its group's pools at
        its index in the group, through its group's table, with its group's
        ``attend`` and its own RoPE; a one-kind model has one group and
        ``kv`` is that group's tuple. Two kinds of attention:

        - every key (the default): gather the table's pages back into
          position order (the block table IS the logical->physical map, so
          indexing the pool with it yields a contiguous ``[rows, L, Hkv, D]``
          view of every sequence, this step's rows included) and
          ``attend(q, k_seq, v_seq) -> [rows, seq, H, D]`` under the
          ``attn/core`` scope;
        - selecting (``attention_topk > 0`` and a table wider than it):
          the indexer keys are scattered beside K/V and
          :meth:`_select_attend` reads only the selected rows. A table of
          at most ``attention_topk`` positions selects everything, which is
          the first kind (its indexer keys are still written: later, wider
          steps read them).

        A latent model (:attr:`latent`) has a third: its layer scatters ONE
        latent row a position, ``c`` and ``k_pe`` where K and V would go,
        and ``attend(q_nope, q_pe, pools, j, tables, w_kvb)`` reads the
        latent of the group's pools at its index ``j`` through the table in
        the program's own form: absorbed, each row's live pages in place
        (the decode step), or expanded over the gathered pages (the prefill
        chunk).

        And two kinds of MLP: dense SwiGLU, or the dropless expert layer
        over the step's rows (a row that writes to the scratch block is
        padding and claims no expert); a model's leading dense layers take
        the first. Returns the pools, the normed activations and the experts
        touched per expert layer (``[layers]`` int32, ``[layers, 2]`` with
        the claims on held experts beside them for an expert share; empty
        for a dense model)."""
        cfg = self.config
        head = (cfg.num_kv_heads or cfg.num_heads, cfg.head_dim)
        pools = list(kv) if self.mixed else [kv]
        live = (views[0].bid != SCRATCH_BLOCK).reshape(x.shape[:2])
        touched = []
        for i in range(cfg.num_layers):
            g, j = self._place[i]
            bid, off, tables, attend = views[g]
            new = bid.shape + head  # this step's K/V rows, one per (bid, off)
            span = tables.shape[-1] * self.engine.block_size
            seq = (x.shape[0], span) + head
            selecting = self.selecting and span > cfg.attention_topk
            lp = params[f"layer_{i}"]
            if self.latent:
                q_nope, q_pe, c, k_pe = self._latent_proj(lp, x, pos, i)
                pools[g] = self._kv_scatter(
                    pools[g], j, bid, off, c.reshape(bid.shape + c.shape[-1:]),
                    k_pe.reshape(bid.shape + k_pe.shape[-1:]),
                )
                ctx = attend(
                    q_nope, q_pe, pools[g], j, tables,
                    lp["attn"]["kv_b_proj"]["kernel"].reshape(
                        self.latent.kv_rank, cfg.num_heads, -1
                    ),
                )
            else:
                q, k, v, index = self._attn_proj(lp, x, pos, i)
                pools[g] = self._kv_scatter(
                    pools[g], j, bid, off, k.reshape(new), v.reshape(new),
                    index and index[2].reshape(bid.shape + index[2].shape[-1:]),
                )
                if selecting:
                    ctx = self._select_attend(
                        pools[g], j, tables.reshape(x.shape[0], -1), q, *index[:2],
                        jnp.where(live, pos, -1),
                    )
                else:
                    k_seq, v_seq = self._kv_gather(pools[g], j, tables)
                    k_seq, v_seq = k_seq.reshape(seq), v_seq.reshape(seq)
                    with annotate("attn/core"):
                        ctx = attend(q, k_seq, v_seq)
            x = self._attn_out(lp, x, ctx)
            if cfg.moe_layer(i):
                x, count = self._moe(lp, x, live)
                touched.append(count)
            else:
                x = self._mlp(lp, x)
        return (
            tuple(pools) if self.mixed else pools[0],
            self._rmsnorm(x, params["final_norm"]["scale"]),
            jnp.stack(touched) if touched else jnp.zeros((0,), jnp.int32),
        )

    # -- jitted decode step --------------------------------------------------
    def decode_step(
        self,
        params: Any,
        kv: tuple[Any, ...],  # pools (+ scales when quantized); a tuple a group
        tables: Any,         # [S, MB] int32 block ids (0-padded); one a group
        lengths: jax.Array,  # [S] int32 known tokens (prompt + generated)
        tokens: jax.Array,   # [S] int32 token fed this step (position len-1)
        active: jax.Array,   # [S] bool
    ) -> tuple[tuple[Any, ...], jax.Array, jax.Array]:
        """One token for each of the table's ``S`` rows, and the experts
        each layer's rows touched (``[layers]`` int32, an output of its own
        that the step's one fetch brings with the tokens; ``[0]`` for a
        dense model). Static per
        PROGRAM: ``S`` and ``MB``, read off ``tables`` (any row count up to
        ``max_slots``, any width up to ``max_blocks_per_seq``; a row is a
        packed position, not a slot). Static per ENGINE: ``block_size``,
        the model, the pools. Rows with ``active`` false are padding: they
        write to the scratch block and their token is garbage.

        Under :attr:`decode_window`, ``tables[s, 0]`` is the block of row
        ``s`` that holds the first position its window can reach
        (:func:`window_first_block` of ``lengths[s]``), not its block 0: a
        row past the window hands over, gathers and attends at most
        :func:`window_blocks` blocks however long it has grown. A model with
        full and window layers takes ``tables = (full [S, MBf], window [S,
        MBw])``: whole tables for the full group, tables from the window's
        first block for the window group; ``S`` is one, the widths two."""
        # Host side effect at TRACE time only: one tick per compilation of
        # this program. A warmed engine calls the AOT executable directly
        # (never retraces), so "zero compiles on the first request" is an
        # assertable counter delta, not a timing heuristic.
        self._tick()
        BS = self.engine.block_size
        x = self._embed(params, tokens)[:, None, :]  # [S, 1, d]
        pos = jnp.maximum(lengths - 1, 0)[:, None]  # [S, 1] absolute
        p = pos[:, 0]

        def view(tables: jax.Array, window: int, cut: int) -> GroupView:
            """One group's step: ``window`` masks, and under ``cut`` (the
            window again) the table starts at the window's first block."""
            # Both static sizes come from the TABLE, not the engine's
            # ceilings: the host packs the rows that decode into this step's
            # row bucket and cuts the table to its width bucket
            # (ServingEngine._decode_shape), so the page gather and the
            # attention over it cost O(rows x width) of what is live. One
            # compile per distinct (S, MB).
            S, MB = tables.shape
            # The new row's block and the query's index, in the TABLE's
            # coordinates: absolute where the table starts at block 0, less
            # the blocks the host left out where it starts at the window's
            # first. RoPE stays absolute (K is stored rotated at its own
            # position) and the attention mask is relative to the index, so
            # nothing below knows the difference.
            first = None
            if cut:
                first = window_first_block(lengths, cut, BS)

            def in_table(x: jax.Array, unit: int) -> jax.Array:
                return x if first is None else x - first * unit

            # Inactive slots route their (garbage) writes to the scratch block.
            bid = jnp.where(
                active,
                tables[jnp.arange(S), jnp.minimum(in_table(p // BS, 1), MB - 1)],
                SCRATCH_BLOCK,
            )
            # Row b attends its own filled prefix 0..lengths[b]-1; negative
            # marks the row inactive (zero output).
            idx = jnp.where(active, in_table(lengths - 1, BS), -1)

            if self.latent:
                # one query a row: the absorbed form reads the latent once
                # for every head, each row's live pages in place

                def attend(q_nope, q_pe, kv, j, tables, w_kvb):
                    return paged_absorbed_attention(
                        q_nope, q_pe, *kv, j, tables, idx, w_kvb,
                        scale=self.latent.scale,
                    )
            else:
                def attend(q: jax.Array, k_seq: jax.Array, v_seq: jax.Array) -> jax.Array:
                    return batched_decode_attention(
                        q, k_seq, v_seq, idx, window=window or None
                    )

            return GroupView(bid, p % BS, tables, attend)

        # decode_window is the window group's (or the one group's) cut; a
        # full group's table is whole
        views = [
            view(t, g.window, self.decode_window if g.window else 0)
            for t, g in zip(tables if self.mixed else (tables,), self.groups)
        ]
        kv, x, touched = self._layers(params, kv, x, pos, views)
        logits = self._logits(x[:, 0], params)  # [S, V] f32
        return kv, jnp.argmax(logits, axis=-1).astype(jnp.int32), touched

    # -- jitted prefill chunk ------------------------------------------------
    def prefill_chunk(
        self,
        params: Any,
        kv: tuple[Any, ...],  # pools (+ scales when quantized); a tuple a group
        table: Any,         # [MB] int32 this slot's block table (0-padded); one a group
        tokens: jax.Array,  # [C] int32 prompt chunk (0-padded past n_valid)
        start: jax.Array,   # scalar int32: absolute position of tokens[0]
        n_valid: jax.Array,  # scalar int32: real rows in the chunk
    ) -> tuple[tuple[Any, ...], jax.Array]:
        """One chunk of one prompt through the slot's block table. Static
        per PROGRAM: the table's width ``MB`` — the host cuts it to the
        bucket covering the positions the chunk can see
        (``start + n_valid``, ServingEngine._prefill_one), so keys past
        the chunk's reach are never gathered, repeated or scored. Static
        per ENGINE: ``prefill_chunk`` and ``block_size``. Rows past
        ``n_valid`` clamp to the table's last position and write to the
        scratch block.

        A model with full and window layers takes ``table = (full [MBf],
        window [MBw])``. The window group's table starts at the first block
        the chunk's FIRST query can reach (:func:`window_first_block` of
        ``start + 1``) and covers ``[start - W + 1, start + C)``; the full
        group's chunk attends in tiles of :data:`SELECT_TILE` queries, so
        that no ``[H, C, L]`` score tensor exists over a long table."""
        # Trace-time compile tick — see decode_step.
        self._tick()
        cfg, e = self.config, self.engine
        BS, C = e.block_size, e.prefill_chunk
        rep = cfg.num_heads // (cfg.num_kv_heads or cfg.num_heads)
        x = self._embed(params, tokens)[None]  # [1, C, d]
        offs = jnp.arange(C, dtype=jnp.int32)
        pos = (start + offs)[None]  # [1, C] absolute

        def view(table: jax.Array, window: int, cut: int, tiled: bool) -> GroupView:
            # As in decode_step: under ``cut`` the table starts at the first
            # block the chunk can reach and every index below is the
            # table's own; the mask is relative, RoPE absolute.
            def in_table(x: jax.Array) -> jax.Array:
                if not cut:
                    return x
                return x - window_first_block(start + 1, cut, BS) * BS

            p = jnp.minimum(in_table(start + offs), table.shape[0] * BS - 1)
            bid = jnp.where(offs < n_valid, table[p // BS], SCRATCH_BLOCK)

            def attend_from(q: jax.Array, k_seq: jax.Array, v_seq: jax.Array, first: Any) -> jax.Array:
                # The chunk's queries see every earlier chunk's pages PLUS
                # this chunk's own rows (just scattered); causal masking in
                # the table's coordinates via q_offset. Stale rows from a
                # previous owner of a recycled block sit at positions
                # strictly after the last valid query and are causally
                # masked.
                return dense_attention(
                    q, k_seq, v_seq,
                    causal=True, window=window or None, q_offset=first,
                )

            if not self.latent:
                def attend(q: jax.Array, k_seq: jax.Array, v_seq: jax.Array) -> jax.Array:
                    k_seq, v_seq = repeat_kv(k_seq, rep), repeat_kv(v_seq, rep)
                    if not tiled:
                        return attend_from(q, k_seq, v_seq, in_table(start))
                    return _by_query_tiles(
                        lambda t: attend_from(t[0], k_seq, v_seq, t[1][0, 0]),
                        (q, in_table(pos)),
                    )
            else:
                # the chunk's queries share every table position's expanded
                # keys and values: expand once, attend in a kernel that
                # keeps its scores on chip (no [H, C, L] score tensor in HBM)
                def attend(q_nope, q_pe, kv, j, table, w_kvb):
                    c, k_pe = (
                        a.reshape((1, -1) + a.shape[-1:])
                        for a in self._kv_gather(kv, j, table)
                    )
                    return chunk_attention(
                        q_nope, q_pe, c, k_pe, w_kvb, scale=self.latent.scale, start=start
                    )

            return GroupView(bid, p % BS, table, attend)

        # Only beside a full group is the window group's chunk table cut and
        # the full group's chunk tiled; a one-kind model's chunk keeps its
        # table from block 0 and attends at once.
        views = [
            view(
                t, g.window,
                cut=self.decode_window if self.mixed and g.window else 0,
                tiled=self.mixed and not g.window,
            )
            for t, g in zip(table if self.mixed else (table,), self.groups)
        ]
        kv, x, _ = self._layers(params, kv, x, pos, views)
        # Only the last VALID row's logits matter (and only on the final
        # chunk — the host ignores them otherwise). Padded rows compute
        # garbage that is never read and whose K/V went to scratch.
        x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        return kv, self._logits(x_last[0, 0], params)

    # -- jitted verify step (speculative decoding) ---------------------------
    def verify_step(
        self,
        params: Any,
        kv: tuple[jax.Array, ...],  # pools (+ scales when quantized)
        tables: jax.Array,   # [S, MB] int32 block ids (0-padded)
        lengths: jax.Array,  # [S] int32 known tokens before this step
        tokens: jax.Array,   # [S, W] int32: last known token + proposals
        n_live: jax.Array,   # [S] int32 fed rows per slot (n_prop + 1)
        active: jax.Array,   # [S] bool
    ) -> tuple[tuple[jax.Array, ...], jax.Array]:
        """One batched multi-token target forward over the paged KV pools.

        The width-``W = spec_k + 1`` extension of :meth:`prefill_chunk`,
        batched over slots: row ``s`` feeds ``tokens[s, i]`` at absolute
        position ``lengths[s] - 1 + i`` (token 0 is the slot's last known
        token — whose K/V is still unwritten, exactly like a plain decode
        step — tokens 1.. are the draft's proposals), scattering each
        position's K/V through the slot's block table and attending the
        full causal prefix of the gathered pages. The returned
        ``argmax[s, i]`` is the target's greedy token for position
        ``lengths[s] + i``: comparing proposals against it IS the
        exact-greedy-match acceptance rule, and K/V written for positions
        past the accepted prefix is garbage-by-construction that the next
        step overwrites before it ever becomes causally visible (same
        stale-row argument as recycled blocks; docs/SERVING.md).

        Per-row query offsets rule out :func:`dense_attention` (its
        ``q_offset`` is one scalar for the whole batch), so the causal
        mask is built inline in absolute coordinates — the numerics
        otherwise mirror ``dense_attention`` line for line (f32 scores,
        f32 softmax, all-masked rows zeroed), which is what keeps the
        verify argmaxes bit-identical to the chunked-prefill/decode path
        the parity tests pin.
        """
        self._tick()
        cfg, BS = self.config, self.engine.block_size
        # Width-bucketed gather, same as decode_step: MB is the host-sliced
        # table width covering this verify batch's deepest row.
        L = tables.shape[1] * BS
        rep = cfg.num_heads // (cfg.num_kv_heads or cfg.num_heads)
        scale = cfg.head_dim**-0.5
        x = self._embed(params, tokens)  # [S, W, d]
        offs = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]  # [1, W]
        pos = jnp.maximum(lengths - 1, 0)[:, None] + offs  # [S, W] absolute
        p = jnp.minimum(pos, L - 1)
        row_valid = active[:, None] & (offs < n_live[:, None])  # [S, W]
        bid = jnp.where(
            row_valid,
            jnp.take_along_axis(tables, p // BS, axis=1),
            SCRATCH_BLOCK,
        )
        k_pos = jnp.arange(L, dtype=jnp.int32)
        # [S, 1, W, L] causal mask in absolute coordinates, per-row offsets.
        valid = (
            (k_pos[None, None, None, :] <= pos[:, None, :, None])
            & row_valid[:, None, :, None]
        )
        if cfg.attention_window:
            valid &= (
                pos[:, None, :, None] - k_pos[None, None, None, :]
                < cfg.attention_window
            )

        def attend(q: jax.Array, k_seq: jax.Array, v_seq: jax.Array) -> jax.Array:
            k_seq, v_seq = repeat_kv(k_seq, rep), repeat_kv(v_seq, rep)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_seq,
                preferred_element_type=jnp.float32,
            ) * scale
            scores = jnp.where(valid, scores, NEG_INF)
            weights = jnp.where(
                jnp.any(valid, axis=-1)[..., None],
                jax.nn.softmax(scores, axis=-1),
                0.0,
            )
            return jnp.einsum(
                "bhqk,bkhd->bqhd", weights.astype(v_seq.dtype), v_seq,
                preferred_element_type=jnp.float32,
            ).astype(q.dtype)

        kv, x, _ = self._layers(
            params, kv, x, pos, [GroupView(bid, p % BS, tables, attend)]
        )
        logits = self._logits(x, params)  # [S, W, V] f32
        return kv, jnp.argmax(logits, axis=-1).astype(jnp.int32)


class ServingEngine:
    """Continuous-batching engine over a ``TransformerLM`` param tree.

    ``clock`` is injectable (tests drive a fake one); ``registry`` is an
    optional ``telemetry.MetricsRegistry`` the engine keeps live serving
    instruments in (queue depth, slot occupancy, KV blocks in use, shed
    count, TTFT/TPOT histograms, speculative acceptance accounting).

    ``draft_config``/``draft_params`` (required iff ``engine.spec_k > 0``)
    define the draft model for speculative decoding — any dense
    ``TransformerLM`` sharing the target's vocab; the usual choice is the
    target's own first N layers (``models.transformer.truncate_lm_params``),
    which reuses the target's tied embedding for the draft logits.

    ``pool``/``kv_buffers`` inject SHARED block accounting and device
    pools — the disaggregation seam (``serving/disagg.py``): a prefill-
    only and a decode-only engine built over the same pool + holder hand
    sequences off by transferring block-table ownership, with the pages
    already in place. Omitted (the default), the engine owns both privately
    — the colocated topology, byte-identical to the pre-disaggregation
    behavior. ``role`` labels this engine's occupancy gauges
    (``serve_queue_depth{role=...}``, :meth:`_role_name`) and its trace
    events: the two engines of a disaggregated pair share one registry.
    """

    def __init__(
        self,
        config: TransformerConfig,
        params: Any,
        engine: EngineConfig | None = None,
        *,
        dtype: Any = jnp.bfloat16,
        eos_id: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        registry: Any = None,
        chaos: Any = None,
        draft_config: TransformerConfig | None = None,
        draft_params: Any = None,
        pool: PagedKVPool | None = None,
        kv_buffers: KVBuffers | None = None,
        draft_kv_buffers: KVBuffers | None = None,
        role: str | None = None,
        prefix_cache: Any = None,
        tenants: dict[str, dict[str, Any]] | None = None,
        tracer: Any = None,
    ) -> None:
        engine = engine or EngineConfig()
        if config.moe_experts > 0 and config.moe_routing != "dropless":
            raise NotImplementedError(
                f"serving engine refuses moe_routing={config.moe_routing!r}: "
                "capacity routing makes a token's output depend on "
                "co-batched strangers, which breaks the engine's "
                "request-independence contract (moe_routing='dropless' "
                "serves every claim and is admitted)"
            )
        if "kernel" not in params["layer_0"]["attn"]["q_a_proj" if config.latent else "q_proj"]:
            raise NotImplementedError(
                "serving engine takes the raw f32 param tree (quantized "
                "trees from ops.quant are not supported)"
            )
        if engine.num_blocks - 1 < engine.max_blocks_per_seq:
            raise ValueError(
                f"pool capacity ({engine.num_blocks - 1} blocks) below "
                f"max_blocks_per_seq ({engine.max_blocks_per_seq}): a "
                "maximum-length request could never be admitted"
            )
        if engine.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {engine.spec_k}")
        if engine.spec_k > 0 and (draft_config is None or draft_params is None):
            raise ValueError(
                "spec_k > 0 needs a draft model: pass draft_config + "
                "draft_params (models.transformer.truncate_lm_params builds "
                "a self-draft from the target's own first N layers)"
            )
        storage = jnp.dtype(engine.kv_dtype) if engine.kv_dtype else None
        if config.attention_topk > 0:
            if engine.spec_k > 0:
                raise NotImplementedError(
                    "attention_topk > 0 with spec_k > 0: the verify step "
                    "does not select (serving/speculative.py)"
                )
            if storage is not None and jnp.issubdtype(storage, jnp.integer):
                raise NotImplementedError(
                    f"attention_topk > 0 with kv_dtype={engine.kv_dtype!r}: "
                    "an integer indexer-key pool is not implemented"
                )
        if config.latent:
            # Latent attention: what this engine does not make work over a
            # latent pool is refused here, by name.
            refused = {
                "spec_k > 0 (the verify step and the draft attend over K/V pools)": engine.spec_k > 0,
                f"kv_dtype={engine.kv_dtype!r} (an integer latent pool)":
                    storage is not None and jnp.issubdtype(storage, jnp.integer),
                "a disaggregated hand-off (injected pool, kv_buffers or role)":
                    pool is not None or kv_buffers is not None or role is not None,
            }
            for what, asked in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"a model with latent attention is not served with {what}"
                    )
        groups = layer_groups(config)
        if len(groups) > 1:
            # Full and window layers side by side: what this engine does
            # not make work for two groups is refused here, by name.
            window = groups[1].window
            refused = {
                "the prefix cache (adoption and copy-on-write over a group "
                "that releases blocks)": engine.prefix_cache or prefix_cache is not None,
                "spec_k > 0 (the verify step and the draft take one table)": engine.spec_k > 0,
                f"kv_dtype={engine.kv_dtype!r} (integer pools a group)":
                    storage is not None and jnp.issubdtype(storage, jnp.integer),
                "a disaggregated hand-off (injected pool, kv_buffers or role)":
                    pool is not None or kv_buffers is not None or role is not None,
                f"a window of {window} that cannot bind within max_seq_len "
                f"{engine.max_seq_len}": window >= engine.max_seq_len,
            }
            for what, asked in refused.items():
                if asked:
                    raise NotImplementedError(
                        "a model with full and window attention layers "
                        f"is not served with {what}"
                    )
            need = self._window_reach(engine, window + engine.prefill_chunk - 1)
            if engine.window_num_blocks - 1 < need:
                raise ValueError(
                    f"window pool capacity ({engine.window_num_blocks - 1} "
                    f"blocks) below the {need} blocks one prefill chunk of "
                    f"{engine.prefill_chunk} under a window of {window} can "
                    "reach: set EngineConfig.window_num_blocks"
                )
        elif engine.window_num_blocks:
            raise ValueError(
                "window_num_blocks is the window group's pool of a model "
                "with full and window layers; this model's layers are of "
                "one kind"
            )
        if storage is not None and jnp.issubdtype(storage, jnp.integer):
            if storage != jnp.dtype(jnp.int8):
                raise NotImplementedError(
                    f"integer KV storage supports int8 only, got "
                    f"{storage.name} (ops.quant.quantize_kv is an int8 "
                    "symmetric scheme)"
                )
        self.config = config
        self.engine = engine
        self.params = params
        self.dtype = dtype
        self.eos_id = eos_id
        self._clock = clock
        self.chaos = chaos
        self.role = role
        if pool is None:
            pool = PagedKVPool(
                engine.num_blocks, engine.block_size, kv_dtype=storage
            )
        elif (
            pool.num_blocks != engine.num_blocks
            or pool.block_size != engine.block_size
        ):
            raise ValueError(
                f"injected pool geometry {pool.num_blocks}x{pool.block_size} "
                f"does not match engine config "
                f"{engine.num_blocks}x{engine.block_size}"
            )
        self.pool = pool
        #: the window group's allocator (a model with full and window
        #: layers; else None): its own free list over its own pools
        self.window_pool = (
            PagedKVPool(engine.window_num_blocks, engine.block_size, kv_dtype=storage)
            if len(groups) > 1 else None
        )
        # Radix prefix cache: built here when enabled, or injected shared
        # (the disaggregated pair indexes ONE cache over its shared pool).
        # Injection implies enabled regardless of the config flag.
        self.prefix_cache = prefix_cache
        if self.prefix_cache is None and engine.prefix_cache:
            from deeplearning_mpi_tpu.serving.prefix_cache import (
                RadixPrefixCache,
            )

            self.prefix_cache = RadixPrefixCache(self.pool, registry=registry)
        self.scheduler = Scheduler(
            self.pool,
            max_slots=engine.max_slots,
            max_seq_len=engine.max_seq_len,
            max_queue=engine.max_queue,
            registry=registry,
            decode_buckets=engine.decode_buckets,
            max_hold_steps=engine.max_hold_steps,
            prefix_cache=self.prefix_cache,
            tenants=tenants,
            window_pool=self.window_pool,
            window_admit_blocks=self.pool.blocks_for(engine.prefill_chunk),
        )
        if kv_buffers is None:
            bufs = tuple(
                init_kv_buffers(
                    len(group.layers), blocks, engine.block_size,
                    config.num_kv_heads or config.num_heads, config.head_dim,
                    storage if storage is not None else dtype,
                    index_dim=config.indexer_head_dim if config.attention_topk else 0,
                    latent_dims=config.latent and (config.kv_lora_rank, config.qk_rope_head_dim),
                )
                for group, blocks in zip(
                    groups, (engine.num_blocks, engine.window_num_blocks)
                )
            )
            kv_buffers = KVBuffers(bufs if len(groups) > 1 else bufs[0])
        self._kvh = kv_buffers
        self._kv_dtype_name = (storage or jnp.dtype(dtype)).name
        self._next_rid = 0
        self.steps = 0
        self._metrics = registry
        # Costless-off tracing (the DMT_SANITIZE pattern): None unless a
        # SpanRecorder was injected; every hot-path hook is a single
        # ``is not None`` test with no allocation behind it.
        self._tracer = tracer
        if registry is not None:
            for name in (
                "serve_requests_submitted", "serve_requests_admitted",
                "serve_requests_completed", "serve_requests_shed",
                "serve_tokens_generated", "serve_prefill_chunks",
                "serve_decode_steps", "serve_program_fallbacks",
                "serve_requeued_total",
                "serve_tokens_discarded_total",
                "serve_gather_blocks", "serve_live_blocks",
                "serve_window_skipped_blocks", "serve_window_released_blocks",
            ):
                registry.counter(name)
            # A role-labeled engine (one half of a disaggregated pair)
            # keeps its occupancy gauges under role=... names — two engines
            # share one registry, and unlabeled gauges would be whichever
            # role stepped last. The coordinator owns the unlabeled
            # combined view.
            for name in (
                "serve_queue_depth", "serve_slots_active",
                "serve_kv_blocks_in_use",
            ):
                registry.gauge(self._role_name(name))
            # Pool footprint by storage dtype: the capacity-multiplier
            # metric metrics_report's per-role table reads ("how many
            # bytes of KV does this engine hold, and in what format").
            from deeplearning_mpi_tpu.telemetry.registry import labeled

            registry.gauge(self._role_name("serve_kv_bytes"))
            registry.gauge(labeled("serve_kv_bytes", dtype=self._kv_dtype_name))
            registry.histogram("serve_ttft_s")
            registry.histogram("serve_tpot_s")
            registry.histogram("serve_compile_seconds")
            registry.counter("serve_compile_total")
            if engine.decode_buckets:
                registry.counter("serve_decode_held_steps")
            if engine.spec_k > 0:
                # The reconciliation invariant every speculative run must
                # satisfy: spec_proposed == spec_accepted + spec_rollback.
                for name in (
                    "spec_proposed_total", "spec_accepted_total",
                    "spec_rollback_total", "spec_verify_steps",
                    "spec_draft_steps", "spec_degraded_total",
                    "spec_blocks_rolled_back_total",
                ):
                    registry.counter(name)
            if self.prefix_cache is not None:
                # Counters live on the cache itself; the occupancy gauges
                # are set alongside the engine's other gauges each step.
                registry.gauge("serve_prefix_nodes")
                registry.gauge("serve_prefix_blocks")
            if config.attention_topk > 0:
                # Per decode step, summed over its rows: keys a row holds,
                # and keys its queries attend (min(length, topk)).
                registry.counter("serve_select_live_keys")
                registry.counter("serve_select_kept_keys")
            if config.moe_experts > 0:
                # Per decode step, summed over layers: distinct experts the
                # step's rows routed to, and experts held.
                registry.counter("serve_moe_experts_touched")
                registry.counter("serve_moe_expert_slots")
            if config.expert_share:
                # Per decode step, summed over expert layers: the live rows'
                # claims, and those that land on an expert held here.
                registry.counter("serve_moe_claims")
                registry.counter("serve_moe_claims_held")
        self._fwd = PagedForward(
            config, engine, dtype,
            tick=lambda: self._inc("serve_compile_total"),
            kv_dtype=storage, window_cut=True,
        )
        # The static shapes a step's block table can take (docs/SERVING.md
        # "The fixed-shape step"): derived from max_slots,
        # max_blocks_per_seq and, for the decode step, the blocks a sliding
        # window can reach; the very lists warmup() walks.
        # A model with full and window layers builds the ladder on the full
        # group's whole tables; its window group's table has ONE width a
        # program kind (what the window, or a chunk under it, can reach:
        # rows not yet past the window pad to it), so a decode program is
        # (rows, full width, that width) and the count does not multiply.
        window = self._fwd.decode_window
        #: the window group's table width in the decode step and in the
        #: prefill chunk (None: one group of layers)
        self._window_widths = (
            self._window_reach(engine, window),
            self._window_reach(engine, window + engine.prefill_chunk - 1),
        ) if self._fwd.mixed else None
        self._widths, self._decode_shapes = _table_shapes(
            engine.max_slots, engine.max_blocks_per_seq,
            self._window_reach(engine, window)
            if window and not self._fwd.mixed else None,
        )
        if config.moe_experts:
            # Request independence: the dropless expert layer rounds its sum
            # in another order in each of its two forms, and the form follows
            # the program's static rows (models.moe.dropless_form). So every
            # decode program takes the form of the ``max_slots``-row one: a
            # row bucket that would take the other is not built, and its
            # rows ride the next bucket up. How many strangers share a step
            # then never moves a completion.
            form = self._moe_form(engine.max_slots)
            self._decode_shapes = tuple(
                s for s in self._decode_shapes if self._moe_form(s[0]) == form
            )
        if config.latent:
            # The latent decode kernel walks each row's live pages whatever
            # the table's width, so a narrower rung saves nothing: one
            # width, the full table, at every row bucket.
            self._decode_shapes = tuple(sorted(
                {(rows, engine.max_blocks_per_seq) for rows, _ in self._decode_shapes}
            ))
        # KV-cache donation, vetoed where unsafe (XLA:CPU + persistent
        # compile cache — compiler.cache.donation_safe, reached through the
        # compat shim): the engine restores weights from disk and then runs
        # these jitted steps, the exact restore-then-execute sequence that
        # corrupts the heap with donated cache-deserialized executables.
        # Donating argument 1 donates every leaf of the kv tuple — data
        # pools and (when quantized) scale pools alike.
        self._kv_donate = (1,) if buffer_donation_supported() else ()
        self._decode_jit = jax.jit(
            self._fwd.decode_step, donate_argnums=self._kv_donate
        )
        self._prefill_jit = jax.jit(
            self._fwd.prefill_chunk, donate_argnums=self._kv_donate
        )
        # CoW copy program (prefix cache only): kv is argument 0 here, so
        # the donation index differs from the model-first programs above.
        self._copy_fn = None
        if self.prefix_cache is not None:
            self._copy_jit = jax.jit(
                self._fwd.copy_block,
                donate_argnums=(0,) if self._kv_donate else (),
            )
            self._copy_fn = self._timed_first_call(self._copy_jit)
        # Lazily-compiling entry points until warmup() swaps in the AOT
        # executables; the wrappers record first-call (= compile) wall time
        # into serve_compile_seconds.
        self._decode_fn = self._timed_first_call(self._decode_jit)
        self._prefill_fn = self._timed_first_call(self._prefill_jit)
        # Armed by warmup(): once True, any serve_compile_total tick is a
        # zero-retrace contract violation the sanitizer (DMT_SANITIZE=1)
        # turns into a SanitizerError instead of a silent latency spike.
        self._warmed = False
        if _sanitizer.enabled():
            _sanitizer.attach_registry(registry)
        self._spec = None
        self._verify_fn = None
        #: brownout stage 2+ (``set_brownout``) suspends speculative
        #: drafts — the verify/accept loop's greedy parity makes falling
        #: back to plain decode a throughput change, never a token change.
        self.spec_suspended = False
        if engine.spec_k > 0:
            from deeplearning_mpi_tpu.serving.speculative import (
                SpeculativeDecoder,
            )

            self._spec = SpeculativeDecoder(
                draft_config, draft_params,
                target_config=config, engine=engine, dtype=dtype,
                tick=lambda: self._inc("serve_compile_total"),
                donate=self._kv_donate,
                kv_dtype=storage,
                kv_buffers=draft_kv_buffers,
                prefix_cache=self.prefix_cache is not None,
            )
            self._verify_jit = jax.jit(
                self._fwd.verify_step, donate_argnums=self._kv_donate
            )
            self._verify_fn = self._timed_first_call(self._verify_jit)

    @staticmethod
    def _window_reach(engine: EngineConfig, positions: int) -> int:
        """The most blocks of a table that ``positions`` consecutive
        positions can touch, a sequence's whole table at most."""
        return min(
            window_blocks(positions, engine.block_size), engine.max_blocks_per_seq
        )

    @property
    def _kv(self) -> tuple[Any, ...]:
        """The live device KV pools — always read through the shared
        holder: a disaggregated peer's step may have donated and replaced
        the arrays since this engine last ran."""
        return self._kvh.bufs

    @_kv.setter
    def _kv(self, bufs: tuple[Any, ...]) -> None:
        self._kvh.bufs = bufs

    def _timed_first_call(self, jitted: Callable[..., Any]) -> Callable[..., Any]:
        """Wrap a jitted program so its first dispatch — the one that pays
        tracing + XLA compilation — lands in ``serve_compile_seconds``. A
        warmed engine replaces this wrapper entirely, so the histogram then
        holds warmup's compile times instead."""
        state = {"first": True}

        def call(*args: Any) -> Any:
            if not state["first"]:
                return jitted(*args)
            state["first"] = False
            t0 = time.perf_counter()
            out = jitted(*args)
            if self._metrics is not None:
                self._metrics.histogram("serve_compile_seconds").observe(
                    time.perf_counter() - t0
                )
            return out

        call.__name__ = jitted.__name__  # launch/dispatch's program label: jit_<name>
        return call

    def warmup(self, *, cache: Any = None) -> dict[str, Any]:
        """AOT-compile the serving programs before traffic.

        Lowers and compiles the batched decode step and the chunked-prefill
        program at every table shape the bucket functions can emit
        (:func:`_table_shapes`), and — when speculative decoding is
        configured — the verify step plus the draft model's decode/prefill
        programs at the full table (every jitted shape is static by design
        — see the module docstring — so warmup's avals are the only avals
        the engine will ever call with), then swaps the compiled executables
        into the hot path wrapped in
        :class:`~deeplearning_mpi_tpu.compiler.aot.WarmProgram`, which picks
        the executable by the table's shape. A compiled
        executable never retraces, so a warmed engine performs ZERO
        compiles on its first request — asserted by the
        ``serve_compile_total`` trace counter in ``tests/test_compiler.py``
        and the ``tools/autotune.py --selftest`` acceptance check.

        ``cache`` is an optional
        :class:`~deeplearning_mpi_tpu.compiler.cache.CompileCache`; under a
        persistent cache directory a restarted engine's warmup
        deserializes instead of compiling (``compile_cache_hit_total``).
        Compile wall time lands in ``serve_compile_seconds``. Returns the
        compiled programs by name.
        """
        from deeplearning_mpi_tpu.compiler import aot

        e = self.engine
        reg = aot.WarmupRegistry(registry=self._metrics, cache=cache)

        def zeros(*shape: int, dtype: Any = jnp.int32) -> jax.Array:
            return jnp.zeros(shape, dtype)

        # One program for every table shape the bucket functions can emit
        # (_table_shapes): the count of these is warm-up's cost. The widest
        # shape of each list keeps the bare name.
        full = (e.max_slots, e.max_blocks_per_seq)
        decode_names = {
            shape: "serve_decode_step" + (
                "" if shape == self._decode_shapes[-1]
                else "@{}x{}".format(*shape)
            )
            for shape in self._decode_shapes
        }
        def tables(*shape: int, kind: int) -> Any:
            """A zero table of ``shape``; for a model with full and window
            layers the pair, the window group's at its one width for this
            ``kind`` of program (0 the decode step, 1 the prefill chunk)."""
            if self._window_widths is None:
                return zeros(*shape)
            return zeros(*shape), zeros(*shape[:-1], self._window_widths[kind])

        for (rows, wb), name in decode_names.items():
            reg.register(
                name, self._decode_jit,
                self.params, self._kv, tables(rows, wb, kind=0),
                zeros(rows), zeros(rows), zeros(rows, dtype=bool),
            )
        prefill_names = {
            (wb,): "serve_prefill_chunk" + ("" if wb == full[1] else f"@{wb}")
            for wb in self._widths
        }
        for (wb,), name in prefill_names.items():
            reg.register(
                name, self._prefill_jit,
                self.params, self._kv, tables(wb, kind=1), zeros(e.prefill_chunk),
                jnp.int32(0), jnp.int32(1),
            )
        if self._spec is not None:
            reg.register(
                "serve_verify_step", self._verify_jit,
                self.params, self._kv, zeros(*full), zeros(e.max_slots),
                zeros(e.max_slots, e.spec_k + 1),
                zeros(e.max_slots), zeros(e.max_slots, dtype=bool),
            )
            self._spec.register_warmup(reg)
        if self.prefix_cache is not None:
            # src/dst are traced scalars: ONE compilation covers every CoW.
            reg.register(
                "serve_kv_copy_block", self._copy_jit,
                self._kv, jnp.int32(0), jnp.int32(0),
            )
        programs = reg.warm_all()
        if self._metrics is not None:
            for prog in programs.values():
                self._metrics.histogram("serve_compile_seconds").observe(
                    prog.lower_seconds + prog.compile_seconds
                )
            # The latent decode kernel's calls in the widest decode program:
            # one a layer of a latent model, none where its entry point fell
            # back to XLA (or where the interpreter ran it).
            self._metrics.gauge("serve_decode_kernel_calls").set(
                aot.mosaic_call_count(
                    programs["serve_decode_step"].compiled, kernel=latent_decode.NAME
                )
            )
        # The table is argument 2 of both programs: its shape picks the
        # executable, so a listed shape never falls through to the jit.
        def fell() -> None:
            self._inc("serve_program_fallbacks")

        self._decode_fn = aot.WarmProgram(
            {s: programs[n] for s, n in decode_names.items()},
            self._decode_jit, shape_arg=2, on_fallback=fell,
        )
        self._prefill_fn = aot.WarmProgram(
            {s: programs[n] for s, n in prefill_names.items()},
            self._prefill_jit, shape_arg=2, on_fallback=fell,
        )
        if self._spec is not None:
            self._verify_fn = aot.WarmProgram(
                programs["serve_verify_step"], self._verify_jit,
                on_fallback=fell,
            )
            self._spec.adopt_warmup(programs, on_fallback=fell)
        if self.prefix_cache is not None:
            self._copy_fn = aot.WarmProgram(
                programs["serve_kv_copy_block"], self._copy_jit,
                on_fallback=fell,
            )
        # The verify step and the draft's decode keep max_slots rows and
        # one executable each, at the full table: their narrower widths are
        # pre-traced through the jit fallbacks. An all-inactive batch routes
        # its writes to the scratch block and rebinds the donated pools, so
        # these calls compile + execute harmlessly and no width transition
        # compiles mid-traffic.
        if self._spec is not None:
            idle = zeros(e.max_slots)
            off = zeros(e.max_slots, dtype=bool)
            for wb in self._widths[:-1]:
                t = zeros(e.max_slots, wb)
                self._kv, _ = self._verify_jit(
                    self.params, self._kv, t, idle,
                    zeros(e.max_slots, e.spec_k + 1), idle, off,
                )
                self._spec.pretrace_width(t, idle, off)
        self._warmed = True
        return programs

    # -- public API ---------------------------------------------------------
    def submit(
        self,
        prompt: Any,
        max_new_tokens: int,
        *,
        deadline: Optional[float] = None,
        arrival: Optional[float] = None,
        tenant: str = "default",
        trace: Optional[str] = None,
    ) -> Request:
        """Enqueue one request (or shed it at the door — check
        ``req.state``). ``prompt`` is a 1-D int sequence.

        ``arrival`` overrides the arrival stamp (same clock as the
        engine's). Re-dispatch paths — a fleet supervisor moving a dead
        replica's request to a survivor — MUST pass the original arrival:
        a fresh stamp would silently grant the request a brand-new SLO
        budget, hiding exactly the deadline misses a failover causes.
        In-process ``recover()`` already keeps it (``Scheduler.requeue``
        preserves ``arrival``/``deadline``); this extends the same
        contract across the process boundary.

        ``trace`` is the cross-process span correlation key (the fleet
        rid); it rides the request so every span this engine emits for it
        stitches into the supervisor's timeline.
        """
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        req = Request(
            rid=self._next_rid,
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
            arrival=self._clock() if arrival is None else arrival,
            deadline=deadline,
            tenant=tenant,
            trace=trace,
        )
        self._next_rid += 1
        self._inc("serve_requests_submitted")
        if not self.scheduler.submit(req):
            self._inc("serve_requests_shed")
        return req

    def cancel(self, req: Request) -> bool:
        """Shed ``req`` wherever it currently lives (hedged-retry dedup —
        the other copy won). False when already finished/shed."""
        if self.scheduler.cancel(req):
            self._inc("serve_requests_shed")
            return True
        return False

    def set_brownout(self, stage: int) -> None:
        """Apply the overload brownout ladder (fleet autoscaler): stage 1+
        sheds lowest-priority tenants at the admission door, stage 2+
        additionally suspends speculative drafts, stage 3 raises the
        deadline floor (all door policy lives in the scheduler)."""
        self.scheduler.set_brownout(stage)
        self.spec_suspended = stage >= 2

    def step(self) -> list[Request]:
        """One engine iteration: shed expired → admit → one prefill chunk
        per PREFILL slot → grow/evict for KV pressure → one batched decode
        (or draft-propose + verify) step → retire finished sequences.
        Returns the requests that FINISHED this step (their freed blocks
        are already back in the pool, ready for the next admission).

        The phases are factored into ``_phase_*`` methods so the
        disaggregated engines (``serving/disagg.py``) can each run exactly
        the subset their role owns — a prefill engine never decodes, a
        decode engine never admits from a prompt queue — against this one
        implementation of each phase.

        Under a profiler session the step is a tree of host spans
        (``telemetry.trace.span``, names under ``serve/``): this parent and
        one child per phase, opened where the work happens so the role
        engines inherit them. The parent's ``t`` label is the engine clock
        at entry — the bridge from the profiler's clock (nanoseconds since
        the session began) to every ``Request.t_*`` stamp and every
        ``SpanRecorder`` record (docs/OBSERVABILITY.md "Clock alignment").
        """
        now = self._clock()
        finished: list[Request] = []
        with span("serve/step", step=self.steps, t=now):
            self._phase_admit(now)
            self._phase_cow()
            self._phase_prefill(finished)
            self._phase_chaos()
            decoding = self._phase_grow()
            self._phase_decode(decoding, finished)
            self.steps += 1
            self._set_gauges()
            if self._tracer is not None:
                # Feeds the flight ring: after a wedge, the ring's tail of
                # engine_step events is the "last known good" timeline.
                self._tracer.event(
                    "engine_step", step=self.steps,
                    role=self.role or "colocated", finished=len(finished),
                )
        return finished

    # -- step phases ---------------------------------------------------------
    def _phase_admit(self, now: float) -> list[Request]:
        """Shed expired queued requests, then admit into free slots."""
        with span("serve/admit") as sp:
            for _ in self.scheduler.shed_expired(now):
                self._inc("serve_requests_shed")
            admitted = self.scheduler.admit(now)
            self._inc("serve_requests_admitted", len(admitted))
            sp.set_metadata(
                admitted=len(admitted), queued=self.scheduler.queue_depth()
            )
        return admitted

    def _phase_cow(self) -> None:
        """Copy-on-write for partially-matched prefix adoptions.

        Runs between admit and prefill: an adopter whose match ends
        mid-block got the shared source pinned (extra pool ref) and a
        private destination at admission; the device copy must land before
        the adopter's first prefill chunk gathers from — and writes into —
        the destination. The pin is dropped either way; a request that
        died between admission and here (external cancel) just unpins.
        """
        if self.prefix_cache is None:
            return
        pending = self.scheduler.take_pending_cow()
        if not pending:
            return
        with span("serve/cow"):
            for src, dst, req in pending:
                if req.state is RequestState.PREFILL:
                    self._kv = dispatch(self._copy_fn, self._kv, *h2d(src, dst))
                    if self._spec is not None:
                        # The draft's pools ride the same block tables, so
                        # the adopted prefix must exist there too — mirror
                        # the copy (same src/dst ids, draft pools).
                        self._spec.copy_block(src, dst)
                    self._record_writes([dst])
                    self.prefix_cache.note_cow()
                self.pool.free([src])  # unpin the CoW source

    def _phase_prefill(self, finished: list[Request]) -> None:
        """One prefill chunk for every PREFILL slot."""
        for req in list(self.scheduler.running()):
            if req.state is RequestState.PREFILL:
                self._prefill_one(req, finished)

    def _phase_chaos(self) -> None:
        if self.chaos is not None:
            # Mid-step, after prefill has already mutated host + device
            # state — the nastiest crash point: admitted requests hold
            # blocks, partial prefills sit in the KV pool, the step never
            # completes. recover() must untangle exactly this.
            self.chaos.check_serve_crash(step=self.steps)

    def _phase_grow(self) -> list[Request]:
        """Mandatory KV growth for every DECODE slot; returns the decode
        batch that survived it."""
        # Feeding a token at position length-1 writes its K/V there, so a
        # slot needs blocks_for(length) blocks BEFORE the step; growth is
        # where OOM pressure surfaces and the scheduler may evict. In
        # speculative mode this growth is what assembles the verify batch,
        # so a pool that cannot serve it sheds the requester under its own
        # labeled reason ("spec_overflow") instead of the generic eviction.
        shed_reason = "spec_overflow" if self._spec is not None else "evicted"
        with span("serve/grow"):
            for req in list(self.scheduler.running()):
                if req.state is not RequestState.DECODE:
                    continue
                while len(req.blocks) < self.pool.blocks_for(req.length):
                    if not self.scheduler.grow(req, shed_reason=shed_reason):
                        self._inc("serve_requests_shed")
                        break
                if self.window_pool is not None and req.state is RequestState.DECODE:
                    self._grow_window(req, req.length)
            # grow() may have evicted requests from the snapshot above.
            return [
                r for r in self.scheduler.running()
                if r.state is RequestState.DECODE
            ]

    def _phase_decode(
        self, decoding: list[Request], finished: list[Request]
    ) -> None:
        """One batched decode (or draft-propose + verify) dispatch, unless
        bucketed batch formation holds it."""
        if decoding and self.scheduler.hold_decode(len(decoding)):
            # Bucketed batch formation: prefill/admission supply can still
            # grow this decode batch toward the next bucket, so spend one
            # of the hold budget's steps on supply instead of dispatching
            # a small batch. Holding only DELAYS decode — emitted tokens
            # are unchanged, so parity is untouched.
            self._inc("serve_decode_held_steps")
            decoding = []
        if decoding:
            if self._spec is not None and not self.spec_suspended:
                self._spec_decode(decoding, finished)
            else:
                self._plain_decode(decoding, finished)

    def _gather_width(self, blocks: int) -> int:
        """Static block-table width covering ``blocks``: the narrowest rung
        of ``_widths`` (:func:`_table_shapes`) that holds them. The
        programs' page gather streams O(width) KV per layer — at serving
        batch sizes that traffic rivals the matmuls — so a shallow fill must
        not pay the full ``max_blocks_per_seq``-wide gather. :meth:`warmup`
        compiles every width, so a warmed engine never compiles on a bucket
        transition."""
        return next(w for w in self._widths if w >= blocks)

    def _moe_form(self, n_tokens: int) -> dict[str, str]:
        """The ``moe`` label of a launch span: the form the dropless expert
        layers of a program over ``n_tokens`` rows take
        (``models.moe.dropless_form``, which the program itself asks: the
        form is static per executable). Empty for a model without experts."""
        cfg = self.config
        if not cfg.moe_experts:
            return {}
        return {"moe": dropless_form(n_tokens, cfg.moe_top_k, cfg.moe_router_width)}

    def _decode_shape(self, rows: int, blocks: int) -> tuple[int, int]:
        """Static (rows, width) of this step's decode table: of the pairs
        in ``_decode_shapes`` (:func:`_table_shapes`) that hold the ``rows``
        that decode and the ``blocks`` the widest live row hands over (what
        it holds, from the first its window can reach), the one of least
        rows x width (the gather and the attention over it are linear in
        that product), with fewer rows on a tie. Both sizes are static per
        PROGRAM, so the step costs what is live."""
        return min(
            (s for s in self._decode_shapes
             if s[0] >= rows and s[1] >= blocks),
            key=lambda s: (s[0] * s[1], s),
        )

    def _plain_decode(
        self, decoding: list[Request], finished: list[Request]
    ) -> None:
        # Only the rows that decode go to the device, packed in the order
        # of ``decoding`` into the warmed table that holds them in the
        # least rows x width; row i of every array — and of the tokens that
        # come back — is decoding[i], whatever its slot. Pad rows are
        # inactive.
        # Under a sliding window that can bind, a row's table starts at the
        # first block its query can reach (the program shifts its indices
        # by the same window_first_block); the blocks before it stay the
        # request's, unread.
        cfg, BS = self.config, self.engine.block_size
        window = self._fwd.decode_window
        first = [
            window_first_block(r.length, window, BS) if window else 0
            for r in decoding
        ]
        labels: dict[str, int] = {}
        if self._fwd.mixed:
            # Two tables: the full group's whole one (what the ladder's
            # width covers), and the window group's from each row's window
            # on, at its one width. A row holds the window group's blocks
            # from ``window_first`` (<= first: what lies behind went back).
            handed = [r.blocks for r in decoding]
            held = [r.window_blocks[f - r.window_first:] for r, f in zip(decoding, first)]
            skipped = sum(f - r.window_first for r, f in zip(decoding, first))
            labels = {
                "live": sum(map(len, handed)),
                "window_width": self._window_widths[0],
                "window_live": sum(map(len, held)),
            }
        else:
            handed = [r.blocks[f:] for r, f in zip(decoding, first)]
            skipped = sum(first)
        reach = list(map(len, handed))
        rows, width = self._decode_shape(len(decoding), max(reach))
        if self._fwd.latent:
            # the live latent positions, and what the table's rectangle gathers
            labels = {
                "attn": "latent_absorbed", "live": sum(r.length for r in decoding),
                "gathered": rows * width * BS,
            }
        form = self._moe_form(rows)
        with span(
            "serve/decode_launch",
            rows=len(decoding), table_rows=rows, width=width,
            skipped=skipped, topk=cfg.attention_topk, **labels, **form,
        ) as launch:
            with span("launch/prep"):
                tables = np.zeros((rows, width), np.int32)
                lengths = np.zeros((rows,), np.int32)
                tokens = np.zeros((rows,), np.int32)
                active = np.zeros((rows,), bool)
                for i, req in enumerate(decoding):
                    tables[i, : reach[i]] = handed[i]
                    lengths[i] = req.length
                    tokens[i] = req.generated[-1]
                    active[i] = True
                if self._fwd.mixed:
                    behind = np.zeros((rows, labels["window_width"]), np.int32)
                    for i, blocks in enumerate(held):
                        behind[i, : len(blocks)] = blocks
                    tables = (tables, behind)
            self._kv, next_tok, touched = dispatch(
                self._decode_fn, self.params, self._kv,
                *h2d(tables, lengths, tokens, active),
            )
            at = [(req.length - 1) // BS for req in decoding]
            self._record_writes({req.blocks[b] for req, b in zip(decoding, at)})
            self._inc("serve_decode_steps")
            self._inc("serve_gather_blocks", rows * width)
            self._inc("serve_live_blocks", sum(reach))
            self._inc("serve_window_skipped_blocks", skipped)
            if self._fwd.mixed:
                # The counters sum the groups. While the program runs: what
                # the NEXT step's window leaves behind goes back to the pool
                # (the device runs its programs in order, so a block handed
                # to another row is written after this step has read it).
                self.window_pool.record_fill(
                    {req.window_blocks[b - req.window_first] for req, b in zip(decoding, at)}
                )
                self._inc("serve_gather_blocks", rows * labels["window_width"])
                self._inc("serve_live_blocks", labels["window_live"])
                launch.set_metadata(released=sum(
                    self._release_behind(req, req.length + 1) for req in decoding
                ))
            if cfg.attention_topk:
                self._inc("serve_select_live_keys", sum(r.length for r in decoding))
                self._inc(
                    "serve_select_kept_keys",
                    sum(min(r.length, cfg.attention_topk) for r in decoding),
                )
        with span("serve/token_fetch"):
            # THE audited sync: one fetch per decode step brings the sampled
            # tokens (EOS/retire decisions are host-side) and, for an expert
            # model, the touched-experts counts beside them (a second array
            # in the same fetch costs ~0.1 ms of host time a step on the
            # v5e, so a dense model fetches the tokens alone).
            if cfg.moe_experts:
                next_np, touched_np = fetch((next_tok, touched))  # dmt-lint: disable=DMT003 — the audited sync
            else:
                next_np = fetch(next_tok)  # dmt-lint: disable=DMT003 — the audited sync
        if cfg.moe_experts:
            counts = touched_np.reshape(cfg.moe_layers, -1)
            self._inc("serve_moe_experts_touched", int(counts[:, 0].sum()))
            self._inc("serve_moe_expert_slots", cfg.moe_layers * cfg.moe_experts)
            if cfg.expert_share:
                self._inc("serve_moe_claims", len(decoding) * cfg.moe_top_k * cfg.moe_layers)
                self._inc("serve_moe_claims_held", int(counts[:, 1].sum()))
        with span("serve/retire") as sp:
            before = len(finished)
            now = self._clock()
            for i, req in enumerate(decoding):
                tok = int(next_np[i])
                req.generated.append(tok)
                self._inc("serve_tokens_generated")
                if self._done(req, tok):
                    self._finish(req, now, finished)
            sp.set_metadata(finished=len(finished) - before)

    def _spec_decode(
        self, decoding: list[Request], finished: list[Request]
    ) -> None:
        """One speculative decode iteration: plan per-slot proposal budgets
        (growing KV cover WITHOUT evicting peers — speculation degrades
        before it preempts), run the draft propose loop, verify the whole
        batch in one jitted step, emit the longest exact-greedy-match
        prefix plus the target's own next token, and roll surplus tail
        blocks back to the free list."""
        e = self.engine
        K, BS = e.spec_k, e.block_size
        with span("serve/draft_launch", rows=len(decoding)):
            with span("launch/prep"):
                tables = np.zeros((e.max_slots, e.max_blocks_per_seq), np.int32)
                lengths = np.zeros((e.max_slots,), np.int32)
                last = np.zeros((e.max_slots,), np.int32)
                n_prop = np.zeros((e.max_slots,), np.int32)
                active = np.zeros((e.max_slots,), bool)
                for req in decoding:
                    s = req.slot
                    # Budget: the step emits up to n+1 tokens; never propose past
                    # the request's remaining generation budget (admission already
                    # bounds prompt + max_new to max_seq_len, so the position
                    # ceiling is subsumed).
                    n = min(K, req.max_new_tokens - len(req.generated) - 1)
                    if n > 0:
                        # Verify writes K/V at positions length-1 .. length-1+n:
                        # take the extra blocks all-or-nothing from the FREE list
                        # only. A speculative tail must never evict a peer (the
                        # mandatory-growth path above handles real pressure);
                        # on a dry pool the budget degrades to what the already-
                        # owned blocks cover.
                        need = self.pool.blocks_for(req.length + n) - len(req.blocks)
                        if need > 0:
                            got = self.pool.alloc(need)
                            if got is None and self.prefix_cache is not None:
                                # Unreferenced cache branches are cheaper than a
                                # degraded proposal budget — evict before giving up
                                # (still never evicting a live peer).
                                if self.prefix_cache.evict(need - self.pool.available):
                                    got = self.pool.alloc(need)
                            if got is not None:
                                req.blocks.extend(got)
                            else:
                                n = min(n, len(req.blocks) * BS - req.length)
                                self._inc("spec_degraded_total")
                    tables[s, : len(req.blocks)] = req.blocks
                    lengths[s] = req.length
                    last[s] = req.generated[-1]
                    n_prop[s] = max(n, 0)
                    active[s] = True
                tables = tables[
                    :, : self._gather_width(max(len(r.blocks) for r in decoding))
                ]
            props, draft_steps = self._spec.propose(
                tables, lengths, last, n_prop, active
            )
            self._inc("spec_draft_steps", draft_steps)
        W = K + 1
        form = self._moe_form(e.max_slots * W)
        with span(
            "serve/verify_launch", rows=len(decoding), width=tables.shape[1],
            **form,
        ):
            with span("launch/prep"):
                tokens = np.zeros((e.max_slots, W), np.int32)
                tokens[:, 0] = last
                tokens[:, 1:] = props
                fed = n_prop + 1
            self._kv, greedy = dispatch(
                self._verify_fn, self.params, self._kv,
                *h2d(tables, lengths, tokens, fed, active),
            )
            touched: set[int] = set()
            for req in decoding:
                n_fed = int(n_prop[req.slot]) + 1
                lo = (req.length - 1) // BS
                hi = min((req.length - 1 + n_fed - 1) // BS, len(req.blocks) - 1)
                touched.update(req.blocks[lo : hi + 1])
            self._record_writes(touched)
            self._inc("serve_decode_steps")
            self._inc("spec_verify_steps")
            # the target's verify gather; the draft's own are not counted
            self._inc("serve_gather_blocks", e.max_slots * tables.shape[1])
            self._inc(
                "serve_live_blocks", sum(len(r.blocks) for r in decoding)
            )
        with span("serve/verify_fetch"):
            greedy_np = fetch(greedy)  # [S, W]  # dmt-lint: disable=DMT003 — the audited verify fetch: exact-match acceptance runs on host
        with span("serve/retire") as sp:
            before = len(finished)
            now = self._clock()
            for req in decoding:
                s = req.slot
                n_p = int(n_prop[s])
                g = greedy_np[s]
                # Exact-greedy-match acceptance: the longest proposal prefix
                # equal to the target's own greedy choices. greedy[i] is the
                # target's token for position lengths[s]+i, i.e. exactly what
                # a plain decode step would emit after the first i proposals.
                n = 0
                while n < n_p and int(props[s, n]) == int(g[n]):
                    n += 1
                emitted_props = 0
                for i in range(n + 1):
                    tok = int(g[i])
                    req.generated.append(tok)
                    self._inc("serve_tokens_generated")
                    if i < n:
                        emitted_props += 1
                    if self._done(req, tok):
                        self._finish(req, now, finished)
                        break
                self._inc("spec_proposed_total", n_p)
                self._inc("spec_accepted_total", emitted_props)
                self._inc("spec_rollback_total", n_p - emitted_props)
                if req.state is RequestState.DECODE:
                    # Roll back the rejected tail's surplus blocks: keep exactly
                    # the cover the next step's mandatory growth would demand,
                    # return the rest to the free list. K/V content needs no
                    # rollback — garbage past the accepted prefix sits at
                    # positions the next verify step overwrites before they
                    # become causally visible.
                    freed = self.scheduler.shrink(
                        req, self.pool.blocks_for(req.length)
                    )
                    self._inc("spec_blocks_rolled_back_total", len(freed))
            sp.set_metadata(finished=len(finished) - before)

    def run_until_idle(self, *, max_steps: int = 100_000) -> list[Request]:
        """Step until queue and slots drain; returns everything finished.

        Injected crashes (:class:`~..resilience.faults.InjectedFault`) are
        recovered in place and the loop continues — each planned fault
        fires exactly once, so this cannot spin. Requests that FINISHED
        during the crashed step stay finished on their own objects (the
        step's return value was lost with the exception; callers assert on
        request state, not on this list, for those).
        """
        from deeplearning_mpi_tpu.resilience.faults import InjectedFault

        finished: list[Request] = []
        steps = 0
        while not self.scheduler.idle():
            try:
                finished.extend(self.step())
            except InjectedFault as err:
                print(f"serving: {err} — recovering")
                self.recover()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps"
                )
        return finished

    def recover(self) -> dict[str, int]:
        """Crash recovery: requeue every in-flight sequence and rebuild the
        KV pool's free list against scheduler ground truth.

        In-flight (PREFILL or DECODE) sequences restart from their prompt:
        after a mid-step crash the engine cannot prove which KV writes
        landed, and re-prefilling from scratch is the only state that is
        both trustworthy and deterministic — it keeps recovered greedy
        completions bit-identical to offline decode. Already-generated
        tokens are discarded (counted in ``serve_tokens_discarded_total``).
        Stale KV rows left by the crashed step are harmless once the pool
        is reconciled: re-prefill overwrites its own pages, and recycled
        blocks' leftover rows sit past every valid position, causally
        masked (the same argument as normal block reuse — and the same one
        covers the draft model's pools, which re-prefill rewrites through
        the same block tables).

        Requeue order preserves FCFS: running requests (admitted earlier
        than anything still queued) are pushed to the queue front,
        newest-arrival first, so the front ends up oldest-first.
        """
        inflight = sorted(self.scheduler.running(), key=lambda r: (r.arrival, r.rid))
        discarded = sum(len(r.generated) for r in inflight)
        for req in reversed(inflight):
            self.scheduler.requeue(req)
        # No sequence owns verified blocks after requeue — but the prefix
        # cache's pages ARE verified (each insert happened after its
        # owner's first-token device sync), so the cache survives: its
        # references are the reconcile ground truth, pending CoW pins are
        # dropped without freeing (reconcile rebuilds every refcount), and
        # requeued requests can still hit the cache on re-admission.
        self.scheduler.clear_pending_cow()
        live: list[int] = []
        if self.prefix_cache is not None:
            live = self.prefix_cache.referenced_blocks()
        stats = self.pool.reconcile(live)
        self.pool.check()
        if self.window_pool is not None:
            # requeue() emptied every request's list: nothing is live
            behind = self.window_pool.reconcile([])
            self.window_pool.check()
            stats = {k: v + behind[k] for k, v in stats.items()}
        self._inc("serve_requeued_total", len(inflight))
        self._inc("serve_tokens_discarded_total", discarded)
        if self.chaos is not None:
            self.chaos.record_recovery("serve_crash")
        self._set_gauges()
        out = {"requeued": len(inflight), "tokens_discarded": discarded, **stats}
        print(
            f"serving: recovered — requeued {out['requeued']} in-flight "
            f"request(s), reclaimed {stats['reclaimed']} KV block(s), "
            f"discarded {discarded} token(s)"
        )
        return out

    # -- prefill ------------------------------------------------------------
    def _prefill_one(self, req: Request, finished: list[Request]) -> None:
        e = self.engine
        start = req.prefilled
        n_valid = min(e.prefill_chunk, req.prompt_len - start)
        # The target's table is cut to the bucket covering the positions
        # this chunk can SEE — not the blocks the request holds, which
        # cover the whole prompt from admission on.
        reach = self.pool.blocks_for(start + n_valid)
        width = self._gather_width(reach)
        labels: dict[str, int] = {}
        if self._fwd.mixed:
            # The window group's blocks come a chunk at a time: take what
            # this chunk writes (the pool may evict for it, this request
            # too), and hand over the blocks from the first its first query
            # can reach.
            if not self._grow_window(req, start + n_valid):
                return
            first = window_first_block(
                start + 1, self._fwd.decode_window, e.block_size
            )
            held = req.window_blocks[first - req.window_first:]
            labels = {
                "window_width": self._window_widths[1], "window_live": len(held),
            }
        if self._fwd.latent:
            labels = {"attn": "latent_expanded", "live": start + n_valid}
        written = slice(
            start // e.block_size, (start + n_valid - 1) // e.block_size + 1
        )
        with span(
            "serve/prefill_launch",
            rid=req.rid, start=start, n=n_valid, width=width,
            topk=self.config.attention_topk, **labels,
            **self._moe_form(e.prefill_chunk),
        ) as launch:
            with span("launch/prep"):
                chunk = np.zeros((e.prefill_chunk,), np.int32)
                chunk[:n_valid] = req.prompt[start : start + n_valid]
                table = np.zeros((e.max_blocks_per_seq,), np.int32)
                table[: len(req.blocks)] = req.blocks
                table = table[:width]
                if self._fwd.mixed:
                    behind = np.zeros((labels["window_width"],), np.int32)
                    behind[: len(held)] = held
                    table = (table, behind)
            table, *args = h2d(table, chunk, start, n_valid)
            self._kv, last_logits = dispatch(
                self._prefill_fn, self.params, self._kv, table, *args
            )
            self._inc("serve_gather_blocks", width)
            self._inc("serve_live_blocks", reach)
            self._record_writes(req.blocks[written])
            if self._fwd.mixed:
                # as in _plain_decode: the counters sum the groups, and what
                # the next chunk (or the first decode step) cannot reach
                # goes back while this one runs
                self.window_pool.record_fill(req.window_blocks[
                    written.start - req.window_first : written.stop - req.window_first
                ])
                self._inc("serve_gather_blocks", labels["window_width"])
                self._inc("serve_live_blocks", len(held))
                launch.set_metadata(
                    released=self._release_behind(req, start + n_valid + 1)
                )
            if self._spec is not None:
                # The draft ingests the prompt alongside the target (same
                # chunk, same table, its own pools) so its propose loop has a
                # complete prefix from the first decode iteration.
                self._spec.prefill_chunk(table, chunk, start, n_valid)
            self._inc("serve_prefill_chunks")
            if self._tracer is not None:
                self._tracer.event(
                    "prefill_chunk",
                    trace=req.trace or f"rid{req.rid}",
                    start=start, n=n_valid,
                    role=self.role or "colocated",
                )
            req.prefilled += n_valid
        if req.prefilled < req.prompt_len:
            return
        # Prompt fully ingested: the first generated token comes straight
        # from the prefill's last-position logits (same seed-step split as
        # models.generate.first_token).
        with span("serve/first_token_fetch", rid=req.rid):
            tok = int(fetch(jnp.argmax(last_logits)))  # dmt-lint: disable=DMT003 — audited: the first token must reach the host to enter req.generated
        with span("serve/retire") as sp:
            before = len(finished)
            req.state = RequestState.DECODE
            req.generated.append(tok)
            req.t_first_token = self._clock()
            self._inc("serve_tokens_generated")
            if self._metrics is not None and req.ttft is not None:
                self._metrics.histogram("serve_ttft_s").observe(req.ttft)
            if self.prefix_cache is not None:
                # Index the FULL prompt blocks now: from this point the request
                # only writes positions >= prompt_len, which never land in a
                # full prefix block, so those pages are frozen. (The partial
                # tail block is still being written by decode; it is indexed at
                # _finish.) The fetch above is the proof the writes
                # landed — insertion after it makes cached pages crash-safe.
                n_full = req.prompt_len // e.block_size
                if n_full:
                    self.prefix_cache.insert(
                        req.prompt, req.blocks, n_full * e.block_size
                    )
            if self._done(req, tok):
                self._finish(req, req.t_first_token, finished)
            else:
                self._prefill_complete(req)
            sp.set_metadata(finished=len(finished) - before)

    def _prefill_complete(self, req: Request) -> None:
        """Hook: ``req`` just finished its prompt (first token emitted) and
        is entering DECODE. No-op in the colocated engine; the
        disaggregated prefill engine overrides this to hand the sequence —
        block table and all — to its decode peer (``serving/disagg.py``)."""

    def _grow_window(self, req: Request, length: int) -> bool:
        """The window group's blocks for ``req``'s first ``length``
        positions (those before ``window_first`` are behind it already),
        evicting under pressure as :meth:`_phase_grow` does for the full
        group. False iff ``req`` itself was shed on the way."""
        need = self.window_pool.blocks_for(length)
        while req.window_first + len(req.window_blocks) < need:
            if not self.scheduler.grow(req, window=True):
                self._inc("serve_requests_shed")
                return False
        return True

    def _release_behind(self, req: Request, length: int) -> int:
        """Give back the window group's blocks that a query at the last of
        ``length`` positions, and so every later one, cannot reach."""
        released = self.scheduler.release_behind(req, window_first_block(
            length, self._fwd.decode_window, self.engine.block_size
        ))
        self._inc("serve_window_released_blocks", released)
        return released

    def _record_writes(self, blocks: Iterable[int]) -> None:
        """Log this dispatch's KV writes against the pool's per-block
        epochs (data + scale move together on quantized pools, which is
        exactly the invariant ``pool.check()`` enforces)."""
        blocks = [b for b in blocks if b != SCRATCH_BLOCK]
        self.pool.record_fill(blocks)
        if self.pool.quantized:
            self.pool.record_scale(blocks)

    # -- retirement ---------------------------------------------------------
    def _done(self, req: Request, tok: int) -> bool:
        if self.eos_id is not None and tok == self.eos_id:
            return True
        return len(req.generated) >= req.max_new_tokens

    def _finish(self, req: Request, now: float, finished: list[Request]) -> None:
        if self.prefix_cache is not None and req.prompt_len % self.engine.block_size:
            # The partial tail block becomes immutable only now (decode was
            # writing generated positions into it); index its frozen span —
            # the prompt positions past the last full block — BEFORE the
            # release below drops the request's own reference.
            self.prefix_cache.insert(req.prompt, req.blocks, req.prompt_len)
        self.scheduler.finish(req, now)
        finished.append(req)
        self._inc("serve_requests_completed")
        if self._metrics is not None and req.tpot is not None:
            self._metrics.histogram("serve_tpot_s").observe(req.tpot)
        if self._tracer is not None:
            self._trace_request(req, now)

    # -- telemetry ----------------------------------------------------------
    def _trace_request(self, req: Request, now: float) -> None:
        """Emit the request's phase spans retroactively from its lifecycle
        stamps — one call at retirement, no open-span tracking through the
        scheduler. The phases tile ``arrival → t_finished`` exactly (the
        only seam, first-token → detach in a disaggregated prefill, is two
        host statements apart), which is what lets ``trace_report`` check
        queue+prefill+handoff+decode against measured TTLT."""
        tr = self._tracer
        trace = req.trace or f"rid{req.rid}"
        root = tr.record_span(
            "request", req.arrival, now, trace=trace,
            rid=req.rid, tenant=req.tenant, tokens=len(req.generated),
            prompt_len=req.prompt_len,
        )
        if req.t_admitted is not None:
            tr.record_span(
                "queue", req.arrival, req.t_admitted,
                trace=trace, parent=root.sid,
            )
            if req.t_first_token is not None:
                tr.record_span(
                    "prefill", req.t_admitted, req.t_first_token,
                    trace=trace, parent=root.sid,
                )
        decode_t0 = req.t_first_token
        if req.t_detached is not None and req.t_adopted is not None:
            tr.record_span(
                "handoff", req.t_detached, req.t_adopted,
                trace=trace, parent=root.sid,
            )
            decode_t0 = req.t_adopted
        if decode_t0 is not None:
            tr.record_span(
                "decode", decode_t0, now, trace=trace, parent=root.sid,
                tokens=len(req.generated),
            )

    def _inc(self, name: str, amount: float = 1.0) -> None:
        if self._metrics is not None and amount:
            self._metrics.counter(name).inc(amount)
        if name == "serve_compile_total":
            # Counter first, tripwire second: a tripped retrace still shows
            # up in serve_compile_total for the post-mortem.
            _sanitizer.check_compile_tick(
                post_warmup=self._warmed, what="serving program"
            )

    def _role_name(self, name: str) -> str:
        """Gauge name for this engine: role-labeled when disaggregated,
        plain otherwise."""
        if self.role is None:
            return name
        from deeplearning_mpi_tpu.telemetry.registry import labeled

        return labeled(name, role=self.role)

    def _set_gauges(self) -> None:
        if self._metrics is None:
            return
        with span("serve/gauges"):
            self._metrics.gauge(self._role_name("serve_queue_depth")).set(
                self.scheduler.queue_depth()
            )
            self._metrics.gauge(self._role_name("serve_slots_active")).set(
                self.scheduler.slots_active()
            )
            self._metrics.gauge(self._role_name("serve_kv_blocks_in_use")).set(
                self.pool.in_use
                + (self.window_pool.in_use if self.window_pool is not None else 0)
            )
            from deeplearning_mpi_tpu.telemetry.registry import labeled

            nbytes = self._kvh.nbytes
            self._metrics.gauge(self._role_name("serve_kv_bytes")).set(nbytes)
            self._metrics.gauge(
                labeled("serve_kv_bytes", dtype=self._kv_dtype_name)
            ).set(nbytes)
            if self.prefix_cache is not None:
                self._metrics.gauge("serve_prefix_nodes").set(
                    self.prefix_cache.num_nodes
                )
                self._metrics.gauge("serve_prefix_blocks").set(
                    self.prefix_cache.num_blocks_cached
                )
            if self.scheduler.tenants:
                inflight = self.scheduler.tenant_tokens_in_flight()
                for tenant in self.scheduler.tenants:
                    self._metrics.gauge(
                        labeled("serve_tenant_tokens_in_flight", tenant=tenant)
                    ).set(inflight.get(tenant, 0))
