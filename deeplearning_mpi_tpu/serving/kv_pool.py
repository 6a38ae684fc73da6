"""Paged KV-cache pool: fixed-size blocks, free-list allocation, block tables.

The offline decode path (``models/generate``) gives every sequence a
contiguous ``[B, prompt + max_new, Hkv, D]`` cache buffer — the right shape
when one jitted program owns the whole batch from prompt to EOS. A serving
engine can't afford that: sequences arrive and finish at different times,
their lengths differ by orders of magnitude, and a contiguous per-sequence
buffer sized for the worst case strands most of its HBM as internal
fragmentation. The paged design (vLLM's PagedAttention, PAPERS: Gemma-on-TPU
serving) fixes the unit of allocation instead: ONE preallocated device pool
of ``num_blocks`` fixed-size blocks per layer, a host-side free list, and a
per-sequence *block table* mapping logical positions to pool blocks. A
sequence holds exactly ``ceil(len / block_size)`` blocks at any moment, and
a finished sequence's blocks return to the free list for the next admission
— the fragmentation bound is one partial block per live sequence.

Split of responsibilities:

- **This module is host-side accounting only** — pure Python, no device
  work, deterministic, and therefore exhaustively testable
  (``tests/test_serving.py`` drives alloc/free storms and checks the
  invariants below).
- The device buffers (``[num_layers, num_blocks, block_size, Hkv, D]`` for
  K and V; for a model with learned sparse attention a third,
  ``[num_layers, num_blocks, block_size, Di]``, for its indexer keys) are
  created by :func:`init_kv_buffers` and owned by the engine,
  which scatters/gathers through the block tables inside its jitted step
  (``serving/engine.py``).

A model with full and window attention layers side by side keeps them in
two **groups** (``serving/engine.py:layer_groups``): one :class:`PagedKVPool`
and one set of device buffers a group, each ``[layers in group, blocks of
group, ...]``, and a request holds a block list in each. The full group's list
covers the whole sequence; the window group's starts at the first block the
window can reach, and the scheduler gives the blocks behind it back to the
window group's pool as the sequence advances
(``Scheduler.release_behind``). Nothing in this class knows which group it
serves.

Block 0 is a reserved **scratch block**, never allocated: the engine's
fixed-shape step always writes *somewhere*, and inactive slots / padded
prefill rows route their writes to block 0 so they can't corrupt a live
sequence's pages.

Invariants (checked by :meth:`PagedKVPool.check`):

- free + in-use = ``num_blocks - 1`` (scratch excluded), always;
- no block is simultaneously free and allocated, or allocated twice;
- allocation is all-or-nothing: a request that can't get every block it
  asked for gets none (no partial reservations to leak under load).

**Refcounted sharing (prefix cache).** A block normally has exactly one
owner; the radix prefix cache (``serving/prefix_cache.py``) makes full
prompt-prefix blocks shared between the cache and every request that
adopted them. :meth:`share` increments a per-block refcount, :meth:`free`
decrements and only returns the block to the free list when the count
reaches zero, and :meth:`record_fill` / :meth:`record_scale` refuse
writes to a block whose refcount is > 1 — a sharer that wants to write
past the frozen span must copy the block first (CoW). The refcount store
is sparse (only counts > 1 are kept; absent means 1) so the unshared hot
path stays allocation-free.
"""

from __future__ import annotations

from typing import Any, Iterable

__all__ = ["PagedKVPool", "SCRATCH_BLOCK", "init_kv_buffers"]

#: Block id reserved for writes that must land nowhere (inactive slots,
#: prefill padding rows). Never on the free list.
SCRATCH_BLOCK = 0


class PagedKVPool:
    """Free-list allocator over ``num_blocks`` KV blocks of ``block_size``
    token positions each. Host-side accounting only; see the module
    docstring for the device-buffer half."""

    def __init__(
        self, num_blocks: int, block_size: int, *, kv_dtype: Any = None
    ) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 scratch + 1 usable), got {num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_dtype = kv_dtype
        # Descending so pop() hands out the lowest id first — deterministic
        # allocation order, which the tests (and debugging) rely on.
        self._free: list[int] = list(range(num_blocks - 1, SCRATCH_BLOCK, -1))
        self._used: set[int] = set()
        # Sparse refcounts for shared blocks: only counts > 1 are stored;
        # a block in _used but absent here has exactly one owner.
        self._refcount: dict[int, int] = {}
        # Monotonic counters for telemetry / the reuse-proving tests.
        self.total_allocated = 0
        self.total_freed = 0
        # Quantized pools carry a scale array next to each data block; the
        # engine must write both in the same step. Per-block write epochs
        # make "data written but scale not" (or vice versa) a checkable
        # invariant instead of a silent garbage gather.
        self._fill_epoch: dict[int, int] = {}
        self._scale_epoch: dict[int, int] = {}
        # Opt-in runtime sanitizer (DMT_SANITIZE=1): freed blocks are
        # poisoned until re-allocated, so double-free and use-after-free
        # fail loud as classified SanitizerErrors instead of the generic
        # accounting ValueError (docs/ANALYSIS.md "Runtime sanitizer").
        self._san = None
        from deeplearning_mpi_tpu.analysis import sanitizer as _sanitizer

        if _sanitizer.enabled():
            self._san = _sanitizer.KVPoolSanitizer()

    @property
    def quantized(self) -> bool:
        """True when the device pools store integer KV + separate scales."""
        if self.kv_dtype is None:
            return False
        import jax.numpy as jnp

        return jnp.issubdtype(jnp.dtype(self.kv_dtype), jnp.integer)

    # -- capacity queries ---------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._used)

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` positions."""
        return -(-num_tokens // self.block_size)

    # -- alloc / free -------------------------------------------------------
    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` blocks off the free list, or ``None`` if fewer than
        ``n`` are free (all-or-nothing — no partial reservation)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._used.update(blocks)
        self.total_allocated += n
        if self._san is not None:
            self._san.on_alloc(blocks)
        return blocks

    def share(self, blocks: Iterable[int]) -> None:
        """Add one owner to each of ``blocks`` (prefix-cache adoption).

        Every block must already be allocated — sharing is always "I now
        also hold what somebody live holds", never a fresh allocation.
        Each sharer must eventually :meth:`free` its reference."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._used:
                raise ValueError(f"sharing block {b} that is not allocated")
        for b in blocks:
            self._refcount[b] = self._refcount.get(b, 1) + 1

    def refcount(self, block: int) -> int:
        """Owners of ``block`` (0 if it is not allocated at all)."""
        if block not in self._used:
            return 0
        return self._refcount.get(block, 1)

    def free(self, blocks: Iterable[int]) -> None:
        """Drop one reference per block; recycle at refcount zero.

        Unshared blocks (the common case) go straight back to the free
        list. Shared blocks just decrement — the last owner's free is the
        one that recycles, so evicting one sharer can never release pages
        another sharer still gathers from. Freeing a block that is not
        allocated (double-free, scratch, out of range) is a caller bug and
        raises — silent tolerance here would mask exactly the accounting
        errors this class exists to prevent. A refcount already below one
        on a block still in the used set is corrupted bookkeeping and is
        classified by the sanitizer as a refcount underflow."""
        blocks = list(blocks)
        if self._san is not None:
            self._san.check_free(blocks, self._used)
        recycled = []
        for b in blocks:
            if b not in self._used:
                raise ValueError(f"freeing block {b} that is not allocated")
            rc = self._refcount.get(b, 1)
            if rc < 1:
                msg = (
                    f"refcount underflow on KV block {b}: count {rc} with "
                    "the block still in the used set — a sharer was freed "
                    "twice or the books were torn"
                )
                if self._san is not None:
                    from deeplearning_mpi_tpu.analysis import sanitizer

                    sanitizer.trip(sanitizer.KV_REFCOUNT_UNDERFLOW, msg)
                raise ValueError(msg)
            if rc > 1:
                if rc == 2:
                    self._refcount.pop(b, None)
                else:
                    self._refcount[b] = rc - 1
                continue
            self._refcount.pop(b, None)
            self._used.remove(b)
            self._free.append(b)
            self.total_freed += 1
            self._fill_epoch.pop(b, None)
            self._scale_epoch.pop(b, None)
            recycled.append(b)
        if self._san is not None and recycled:
            self._san.on_free(recycled)

    # -- quantized-pool write accounting ------------------------------------
    def _check_cow(self, b: int, kind: str) -> None:
        """Writes to a shared block are forbidden: every sharer reads the
        same frozen pages, so a writer must copy first (CoW)."""
        if self._refcount.get(b, 1) <= 1:
            return
        msg = (
            f"{kind} write recorded against shared KV block {b} "
            f"(refcount {self._refcount[b]}): the writer skipped "
            "copy-on-write and is mutating pages other sharers still read"
        )
        if self._san is not None:
            from deeplearning_mpi_tpu.analysis import sanitizer

            sanitizer.trip(sanitizer.KV_COW_VIOLATION, msg)
        raise ValueError(msg)

    def record_fill(self, blocks: Iterable[int]) -> None:
        """Note that the engine scattered KV *data* into ``blocks`` this
        step. Paired with :meth:`record_scale` on quantized pools; the
        scratch block is ignored (its writes are garbage by design)."""
        blocks = list(blocks)
        if self._san is not None:
            self._san.check_touch(blocks, self._used, "data")
        for b in blocks:
            if b == SCRATCH_BLOCK:
                continue
            if b not in self._used:
                raise ValueError(f"recording fill of unallocated block {b}")
            self._check_cow(b, "data")
            self._fill_epoch[b] = self._fill_epoch.get(b, 0) + 1

    def record_scale(self, blocks: Iterable[int]) -> None:
        """Note that the engine scattered *scale* rows into ``blocks`` this
        step (quantized pools only)."""
        blocks = list(blocks)
        if self._san is not None:
            self._san.check_touch(blocks, self._used, "scale")
        for b in blocks:
            if b == SCRATCH_BLOCK:
                continue
            if b not in self._used:
                raise ValueError(f"recording scale of unallocated block {b}")
            self._check_cow(b, "scale")
            self._scale_epoch[b] = self._scale_epoch.get(b, 0) + 1

    def reconcile(self, live_blocks: Iterable[int]) -> dict[str, int]:
        """Rebuild the free list from the ground truth of which blocks are
        still owned by live sequences (crash recovery).

        After a mid-step crash the pool's incremental accounting can
        disagree with scheduler state in both directions — blocks a
        requeued sequence abandoned (leaked: used here, owned by nobody)
        and blocks the crash interrupted mid-alloc (orphaned: owned by a
        sequence, missing from ``_used``). Instead of patching case by
        case, rebuild: ``live_blocks`` becomes the used set and everything
        else becomes free. Returns ``{"reclaimed": leaked, "adopted":
        orphaned}`` for the recovery log; :meth:`check` passes by
        construction afterwards.

        ``live_blocks`` may contain duplicates: each occurrence is one
        live reference, and the multiplicity becomes the rebuilt refcount
        (the prefix cache reports its retained blocks alongside any
        surviving sequences' block tables, so a shared block rebuilds with
        every owner counted — recovery can neither leak a shared block nor
        double-free it when the sharers drain).
        """
        from collections import Counter

        counts = Counter(live_blocks)
        live = set(counts)
        if SCRATCH_BLOCK in live:
            raise ValueError("scratch block claimed as live")
        bad = [b for b in live if not (0 < b < self.num_blocks)]
        if bad:
            raise ValueError(f"live block ids out of range: {bad}")
        reclaimed = self._used - live
        adopted = live - self._used
        self.total_freed += len(reclaimed)
        self.total_allocated += len(adopted)
        self._used = set(live)
        self._refcount = {b: c for b, c in counts.items() if c > 1}
        all_ids = set(range(SCRATCH_BLOCK + 1, self.num_blocks))
        self._free = sorted(all_ids - live, reverse=True)
        # Epochs restart from a consistent baseline: reclaimed blocks lose
        # theirs with the block, survivors keep whatever matched state they
        # had, adopted blocks start at zero (their pages will be rewritten
        # by the requeued prefill anyway).
        self._fill_epoch = {b: self._fill_epoch.get(b, 0) for b in live}
        if self.quantized:
            # A crash can land between the data and scale scatters; recovery
            # requeues and re-prefills every live sequence, so declare the
            # surviving pages consistent by fiat rather than tripping check()
            # on a tear the rewrite is about to erase.
            self._scale_epoch = dict(self._fill_epoch)
        else:
            self._scale_epoch = {
                b: self._scale_epoch.get(b, 0) for b in live
            }
        return {"reclaimed": len(reclaimed), "adopted": len(adopted)}

    # -- invariants ---------------------------------------------------------
    def check(self) -> None:
        """Raise AssertionError if any pool invariant is violated."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate ids on the free list"
        assert not (free & self._used), "block both free and allocated"
        assert SCRATCH_BLOCK not in free and SCRATCH_BLOCK not in self._used, (
            "scratch block entered circulation"
        )
        assert len(free) + len(self._used) == self.capacity, (
            f"leak: {len(free)} free + {len(self._used)} used "
            f"!= {self.capacity}"
        )
        stray = (set(self._fill_epoch) | set(self._scale_epoch)) - self._used
        assert not stray, f"write epochs recorded for non-live blocks {stray}"
        rc_stray = set(self._refcount) - self._used
        assert not rc_stray, f"refcounts recorded for non-live blocks {rc_stray}"
        rc_bad = {b: c for b, c in self._refcount.items() if c <= 1}
        assert not rc_bad, (
            f"non-sparse refcounts {rc_bad}: counts <= 1 must not be stored"
        )
        if self.quantized:
            torn = [
                b
                for b in self._used
                if self._fill_epoch.get(b, 0) != self._scale_epoch.get(b, 0)
            ]
            assert not torn, (
                f"stale scales: data/scale write epochs diverge on blocks "
                f"{torn} — a gather here would dequantize with the wrong "
                f"scale"
            )


def init_kv_buffers(
    num_layers: int,
    num_blocks: int,
    block_size: int,
    kv_heads: int,
    head_dim: int,
    kv_dtype: Any,
    *,
    index_dim: int = 0,
    latent_dims: tuple[int, int] | None = None,
) -> tuple[Any, ...]:
    """Zero-initialized device pools in the explicit storage ``kv_dtype``.

    ``latent_dims = (kv_rank, rope)`` (a model with multi-head latent
    attention, float storage only) is the latent pool: a position's ONE
    cached vector ``[c ; k_pe]`` a layer, every head's keys and values
    expanded from it, and no V pool; ``kv_heads`` and ``head_dim`` size
    nothing. It is kept as two arrays, ``(c, k_pe)`` of ``[num_layers,
    num_blocks, block_size, kv_rank]`` and ``[num_layers, num_blocks, rope,
    block_size]``, and not as one of ``kv_rank + rope``: the TPU tiles an
    array's last two dims by (8, 128), and a last dim of 576 (DeepSeek-V3's
    512 + 64) is not a multiple of 128, so XLA keeps such a pool in another
    layout and copies the whole of it back and forth around every layer's
    scatter and gather (two 3.5 GB copies a layer in a described-v5e compile
    of the decode step at 4,801 blocks of 128). ``c`` of 512 tiles as it is;
    a block of ``k_pe`` holds its positions minor (``[rope, block_size]``),
    the layout XLA gave a ``[block_size, 64]`` block anyway (its 64 values
    would fill half of each tile's 128 lanes), so that the decode kernel
    (``ops/pallas/latent_decode.py``) copies a block of it as whole tiles.
    The two arrays take the places of K and V: one block table serves
    both.

    ``index_dim > 0`` (a model with learned sparse attention, float storage
    only) adds a THIRD pool, the indexer keys: ``(k, v, k_index)`` with
    ``k_index`` ``[num_layers, num_blocks, block_size, index_dim]`` — one
    small key a position, addressed by the same block tables, so one
    allocation covers all three and a block copy carries all three.

    Float dtypes return ``(k, v)``, each ``[num_layers, num_blocks,
    block_size, kv_heads, head_dim]``. Integer dtypes (the int8 KV cache)
    additionally return per-token-row scale pools — ``(k, v, k_scale,
    v_scale)`` with scales shaped ``[num_layers, num_blocks, block_size,
    kv_heads]`` in f32, one absmax scale per cached row per head (see
    ``ops/quant.quantize_kv``).

    One array per K/V (not per layer) so the jitted engine step threads a
    handful of buffers instead of ``2 * num_layers`` — the step's Python
    layer loop puts the static layer number into the scatter's and the
    gather's own index (``pool[i, blocks]``, never ``pool[i][blocks]``,
    whose slice is a copy of the layer's whole pool).
    """
    import jax.numpy as jnp

    if latent_dims:
        if jnp.issubdtype(jnp.dtype(kv_dtype), jnp.integer):
            raise NotImplementedError("a latent pool in integer storage is not implemented")
        kv_rank, rope = latent_dims
        return (
            jnp.zeros((num_layers, num_blocks, block_size, kv_rank), kv_dtype),
            jnp.zeros((num_layers, num_blocks, rope, block_size), kv_dtype),
        )
    shape = (num_layers, num_blocks, block_size, kv_heads, head_dim)
    k = jnp.zeros(shape, kv_dtype)
    v = jnp.zeros(shape, kv_dtype)
    if not jnp.issubdtype(jnp.dtype(kv_dtype), jnp.integer):
        if index_dim:
            index_shape = (num_layers, num_blocks, block_size, index_dim)
            return k, v, jnp.zeros(index_shape, kv_dtype)
        return k, v
    if index_dim:
        raise NotImplementedError(
            "an indexer-key pool in integer storage is not implemented "
            "(quantize_kv is a per-head scheme for K and V)"
        )
    # Scales default to 1 (not 0): a gather from a never-written block then
    # dequantizes zeros to zeros instead of 0 * 0 hiding a missing write
    # behind an all-zero page that happens to look plausible.
    sshape = (num_layers, num_blocks, block_size, kv_heads)
    ones = jnp.ones(sshape, jnp.float32)
    return k, v, ones, jnp.ones(sshape, jnp.float32)
