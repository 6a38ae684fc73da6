"""Draft-model speculative decoding for the paged serving engine.

Plain decode emits one token per jitted step, and at serving batch sizes
each step's cost is dominated by streaming the target model's weights —
the arithmetic for one token per row is nearly free next to the memory
traffic. Speculative decoding (Leviathan et al.; Chen et al. 2023) buys
more tokens per weight-stream: a cheap DRAFT model proposes ``k`` tokens
per sequence, and the target model scores all ``k + 1`` positions in ONE
batched forward (``PagedForward.verify_step``) whose weight traffic is the
same as a single decode step. Because this engine is greedy-only, the
acceptance rule collapses to **exact greedy match**: a proposal is
accepted iff it equals the target's own argmax at that position, so the
emitted stream is bit-identical to plain greedy decode for ANY draft —
a bad draft costs throughput (rejections), never correctness. That is the
same parity oracle ``tests/test_serving.py`` pins for the plain engine,
now covering the speculative path.

The draft here is a full ``TransformerLM`` sharing the target's vocab —
usually the target's own first N layers via
``models.transformer.truncate_lm_params`` (a "self-draft": the tied
embedding doubles as the draft's output head, so the draft reuses the
target's logit geometry and needs no training of its own), but any dense
config/params pair works. The draft keeps its OWN paged KV pools (its
layer count and head dims differ from the target's) written through the
SAME block tables and free list: block geometry (``block_size``,
``max_blocks_per_seq``) is an engine property, not a model property, so
one allocation decision covers both models and eviction/rollback never
needs draft-specific bookkeeping.

Draft KV discipline (the part that is easy to get wrong): before a
propose loop at known length ``L``, the draft's cache must be correct for
positions ``0..L-2`` — position ``L-1`` belongs to the token being fed.
The prompt part comes from ``prefill_chunk`` (run alongside the target's
prefill). During propose, step ``j`` writes position ``L-1+j``; the
accepted prefix of those writes used exactly the tokens that were
emitted, so the invariant self-maintains, and the loop deliberately runs
one step past the last collected proposal (``j = n_prop``) so a FULLY
accepted round still leaves position ``L'-2`` written. Rejected-tail
positions hold garbage that the next round overwrites at the exact step
each position first becomes causally visible — the same
overwrite-before-read argument the engine makes for recycled blocks.
Crash recovery and eviction need no draft handling at all: re-prefill
rewrites the draft pools through the same tables.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_mpi_tpu.models.transformer import TransformerConfig
from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers
from deeplearning_mpi_tpu.serving.launch import dispatch, fetch, h2d
from deeplearning_mpi_tpu.telemetry.trace import span

__all__ = ["SpeculativeDecoder"]


class SpeculativeDecoder:
    """The draft side of speculative decoding: owns the draft model's
    params, paged KV pools, and jitted propose/prefill programs. The
    engine drives it with host numpy arrays shaped exactly like its own
    slot-indexed decode inputs; :meth:`propose` is also the seam tests
    override to script adversarial or oracle proposal streams (the
    engine's verify step guards correctness either way)."""

    def __init__(
        self,
        config: TransformerConfig,
        params: Any,
        *,
        target_config: TransformerConfig,
        engine: Any,  # EngineConfig (not imported: engine.py imports us)
        dtype: Any,
        tick: Callable[[], None] | None = None,
        donate: tuple[int, ...] = (),
        kv_dtype: Any = None,
        kv_buffers: Any = None,
        prefix_cache: bool = False,
    ) -> None:
        if config.vocab_size != target_config.vocab_size:
            raise ValueError(
                "draft and target must share one tokenizer: vocab "
                f"{config.vocab_size} != {target_config.vocab_size}"
            )
        if config.moe_experts > 0:
            raise NotImplementedError("draft model must be dense (no MoE)")
        if "kernel" not in params["layer_0"]["attn"]["q_proj"]:
            raise NotImplementedError(
                "draft takes the raw f32 param tree (no quantized trees)"
            )
        # engine.py imports this module lazily; import the forward the same
        # way to keep the cycle one-directional at module load.
        from deeplearning_mpi_tpu.serving.engine import KVBuffers, PagedForward

        self.config = config
        self.params = params
        self.engine = engine
        self.spec_k = engine.spec_k
        self._fwd = PagedForward(
            config, engine, dtype, tick=tick, kv_dtype=kv_dtype
        )
        # Same storage dtype as the target: the int8 capacity win applies
        # to the draft's pools too. ``kv_buffers`` injects a SHARED holder
        # (disaggregation: the prefill role's draft writes the prompt, the
        # decode role's draft proposes from it); omitted, the draft owns
        # its pools privately, exactly as before.
        if kv_buffers is None:
            kv_buffers = KVBuffers(init_kv_buffers(
                config.num_layers, engine.num_blocks, engine.block_size,
                config.num_kv_heads or config.num_heads, config.head_dim,
                kv_dtype if kv_dtype is not None else dtype,
            ))
        self._kvh = kv_buffers
        self._decode_jit = jax.jit(
            self._fwd.decode_step, donate_argnums=donate
        )
        self._prefill_jit = jax.jit(
            self._fwd.prefill_chunk, donate_argnums=donate
        )
        self._decode_fn: Callable[..., Any] = self._decode_jit
        self._prefill_fn: Callable[..., Any] = self._prefill_jit
        # Prefix-cache CoW mirror: an adopted prefix exists in the draft's
        # pools too (written by the original prefill through the shared
        # tables), so the engine mirrors every target-pool block copy here
        # — cache hits then keep the draft's prefix KV valid and the
        # acceptance rate intact.
        self._copy_jit = None
        self._copy_fn: Callable[..., Any] | None = None
        if prefix_cache:
            self._copy_jit = jax.jit(
                self._fwd.copy_block, donate_argnums=(0,) if donate else ()
            )
            self._copy_fn = self._copy_jit

    @property
    def _kv(self) -> tuple[Any, ...]:
        return self._kvh.bufs

    @_kv.setter
    def _kv(self, bufs: tuple[Any, ...]) -> None:
        self._kvh.bufs = bufs

    # -- warmup (driven by ServingEngine.warmup) -----------------------------
    def register_warmup(self, reg: Any) -> None:
        e = self.engine
        reg.register(
            "serve_draft_decode_step", self._decode_jit,
            self.params, self._kv,
            jnp.zeros((e.max_slots, e.max_blocks_per_seq), jnp.int32),
            jnp.zeros((e.max_slots,), jnp.int32),
            jnp.zeros((e.max_slots,), jnp.int32),
            jnp.zeros((e.max_slots,), bool),
        )
        reg.register(
            "serve_draft_prefill_chunk", self._prefill_jit,
            self.params, self._kv,
            jnp.zeros((e.max_blocks_per_seq,), jnp.int32),
            jnp.zeros((e.prefill_chunk,), jnp.int32),
            jnp.int32(0), jnp.int32(1),
        )
        if self._copy_jit is not None:
            reg.register(
                "serve_draft_copy_block", self._copy_jit,
                self._kv, jnp.int32(0), jnp.int32(0),
            )

    def adopt_warmup(
        self, programs: dict[str, Any],
        on_fallback: Callable[[], None] | None = None,
    ) -> None:
        from deeplearning_mpi_tpu.compiler import aot

        self._decode_fn = aot.WarmProgram(
            programs["serve_draft_decode_step"], self._decode_jit,
            on_fallback=on_fallback,
        )
        self._prefill_fn = aot.WarmProgram(
            programs["serve_draft_prefill_chunk"], self._prefill_jit,
            on_fallback=on_fallback,
        )
        if self._copy_jit is not None:
            self._copy_fn = aot.WarmProgram(
                programs["serve_draft_copy_block"], self._copy_jit,
                on_fallback=on_fallback,
            )

    def pretrace_width(
        self, tables: Any, idle: Any, off: Any
    ) -> None:
        """Compile the draft decode program for one narrower gather-width
        bucket (ServingEngine.warmup drives this with all-inactive rows —
        scratch-block writes, harmless execution)."""
        self._kv, _, _ = self._decode_jit(
            self.params, self._kv, tables, idle, idle, off
        )

    # -- engine hooks --------------------------------------------------------
    def copy_block(self, src: int, dst: int) -> None:
        """Mirror the target pools' CoW copy in the draft pools (engine
        ``_phase_cow``; same physical block ids — the tables are shared)."""
        assert self._copy_fn is not None, "draft built without prefix_cache"
        self._kv = dispatch(self._copy_fn, self._kv, *h2d(src, dst))

    def prefill_chunk(
        self,
        table: np.ndarray,
        chunk: np.ndarray,
        start: int,
        n_valid: int,
    ) -> None:
        """Ingest one prompt chunk into the draft's KV pools (same chunk,
        same block table, draft dims); the logits are discarded — the
        target's prefill owns the first generated token."""
        self._kv, _ = dispatch(
            self._prefill_fn, self.params, self._kv,
            *h2d(table, chunk, start, n_valid),
        )

    def propose(
        self,
        tables: np.ndarray,   # [S, MB] int32 block tables (0-padded)
        lengths: np.ndarray,  # [S] int32 known tokens per slot
        last: np.ndarray,     # [S] int32 each slot's last known token
        n_prop: np.ndarray,   # [S] int32 proposal budget per slot (<= K)
        active: np.ndarray,   # [S] bool
    ) -> tuple[np.ndarray, int]:
        """Run the draft autoregressively for this engine step.

        Step ``j`` feeds each active row's current token at absolute
        position ``lengths - 1 + j`` (writing its draft K/V there) and
        argmaxes the draft logits into proposal ``j``. Rows whose budget
        is exhausted go inactive (scratch writes, ignored outputs), and
        the loop runs through ``j = max(n_prop)`` — one step PAST the last
        collected proposal — so a fully-accepted round leaves the draft
        cache complete (see the module docstring). Returns the ``[S, K]``
        proposal matrix and the number of draft steps spent (the engine's
        ``spec_draft_steps`` counter).
        """
        S = tables.shape[0]
        K = self.spec_k
        props = np.zeros((S, K), np.int32)
        cur = np.asarray(last, np.int32).copy()
        act_rows = np.asarray(active, bool)
        budget = np.asarray(n_prop, np.int32)
        last_j = int(budget[act_rows].max()) if act_rows.any() else 0
        steps = 0
        for j in range(min(last_j, K) + 1):
            with span("launch/prep"):
                act = act_rows & (j <= budget)
                at = (lengths + j).astype(np.int32)
            self._kv, out, _ = dispatch(
                self._decode_fn, self.params, self._kv,
                *h2d(tables, at, cur, act),
            )
            steps += 1
            out_np = fetch(out).astype(np.int32)  # dmt-lint: disable=DMT003 — the draft's one audited fetch per propose step: proposals feed the host-side accept loop
            if j < K:
                take = act & (j < budget)
                props[take, j] = out_np[take]
            cur = np.where(act, out_np, cur).astype(np.int32)
        return props, steps
