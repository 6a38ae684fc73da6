"""Decoder-only Transformer LM in Flax linen — the long-context flagship.

The reference has no sequence models (both workloads are CNNs,
``pytorch/unet/model.py:51-81``, ``pytorch/resnet/main.py:40``), but this
framework treats long-context and multi-axis parallelism as first-class, and
the transformer is the workload that exercises them: sequence/context
parallelism (ring attention over the mesh ``seq`` axis), tensor parallelism
(``model`` axis), pipeline stages (``pipe``), and MoE experts (``expert``).

TPU-first choices:
- bf16 activations / f32 parameters; every norm and softmax accumulates f32.
- Separate Q/K/V projections so megatron-style column sharding over the
  ``model`` axis splits along head boundaries (fused QKV would interleave
  q/k/v in one column space and shard across their boundary).
- RoPE positions (no learned position table to shard or resize).
- Pre-norm residual blocks (RMSNorm), SwiGLU MLP — the standard
  modern-LM block; everything jit-traceable with static shapes.
- ``attention_fn`` injection point: the module computes Q/K/V and hands them
  to a callable, so dense attention, the Pallas flash kernel, and
  sequence-parallel ring attention are swappable without touching the model.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning_mpi_tpu.ops.attention import (
    decode_attention,
    dense_attention,
    repeat_kv,
)
from deeplearning_mpi_tpu.ops.latent_attention import expanded_attention
from deeplearning_mpi_tpu.ops.sparse_attention import sparse_attention

# (q, k, v [B,S,H,D], causal=...) -> context [B,S,H,D]
AttentionFn = Callable[..., jax.Array]


def attention_fn_layout(fn: AttentionFn | None) -> str:
    """Layout an attention fn expects: its ``layout`` attribute, followed
    through ``functools.partial`` chains (``partial`` does not forward
    attributes, and a partial-wrapped BHSD entry silently treated as BSHD
    would compute attention with the S and H axes swapped — same output
    shape, wrong numbers). Bare lambdas/closures around a BHSD entry must
    re-attach ``.layout`` themselves."""
    while fn is not None:
        layout = getattr(fn, "layout", None)
        if layout is not None:
            return layout
        fn = getattr(fn, "func", None)  # functools.partial unwrapping
    return "bshd"


def attention_fn_accepts_gqa(fn: AttentionFn | None) -> bool:
    """Whether the attention fn consumes GROUPED K/V natively (its
    ``gqa_native`` attribute, through ``partial`` chains — same mechanics
    as :func:`attention_fn_layout`). The ring factory sets it: rotating
    Hkv-head blocks divides ring ICI volume by H/Hkv; everything else
    receives ``repeat_kv``'d tensors as before."""
    while fn is not None:
        native = getattr(fn, "gqa_native", None)
        if native is not None:
            return bool(native)
        fn = getattr(fn, "func", None)
    return False


def yarn_inv_freq(
    head_dim: int, base: float, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's rotary frequencies, ``[head_dim // 2]`` float32, as
    ``transformers``' ``_compute_yarn_parameters`` has them: dimension ``j``
    turns at ``f_j = base^(-2j/d)``; dimensions that make more than
    ``beta_fast`` turns over the original context keep ``f_j``
    (extrapolated), those that make fewer than ``beta_slow`` get ``f_j /
    factor`` (interpolated), a linear ramp between. Host numbers: a layer's
    frequencies are constants of its program."""
    half = head_dim // 2
    f = base ** (-np.arange(half, dtype=np.float64) / half)

    def corr(turns: float) -> float:  # the dimension that makes ``turns`` turns
        return head_dim * math.log(original_max / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    base: float = 10000.0,
    layout: str = "bshd",
    inv_freq: Any = None,
    factor: float = 1.0,
) -> jax.Array:
    """Rotary position embedding over ``[B, S, H, D]`` (D even).

    ``inv_freq`` (``[D // 2]``) replaces the frequencies ``base`` gives, and
    ``factor`` scales cos and sin alike (so q.k grows by its square): the
    two things a frequency-scaled RoPE (:func:`yarn_inv_freq`) changes.

    Angles and cos/sin are computed in f32 — bf16 *phase* accumulation
    drifts at long context — but the rotation arithmetic runs in ``x``'s
    own dtype: the tables are exact to within one rounding at any position,
    and keeping the big ``[B,S,H,D]`` tensor out of f32 matters — an f32
    round-trip here materialized ~2.4 GB/step of layout copies in the 110M
    LM benchmark (profiled; 50 MB per q/k per layer per direction), one of
    the larger single sources of HBM traffic in the whole step.

    ``layout='bhsd'`` rotates ``[B, H, S, D]`` instead (the flash kernels'
    native layout) — same math, the broadcast axis moves; elementwise, so
    no layout copy either way.
    """
    half = x.shape[-1] // 2
    if inv_freq is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B, S, half]
    # [B, 1, S, half] or [B, S, 1, half]: the head axis broadcasts
    heads = (slice(None), None) if layout == "bhsd" else (slice(None), slice(None), None)
    scaled = (lambda t: t * factor) if factor != 1.0 else (lambda t: t)
    cos = scaled(jnp.cos(angles))[heads].astype(x.dtype)
    sin = scaled(jnp.sin(angles))[heads].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _dense_factory(quantized: bool, dtype: Any):
    """Bias-free projection constructor: ``fn(features, name)`` building
    either ``nn.Dense`` or (inference-only) ``ops.quant.QuantDense`` — one
    definition so Attention and SwiGLU can't diverge on how quantized
    kernels are constructed."""
    if quantized:
        from deeplearning_mpi_tpu.ops.quant import QuantDense

        return lambda feats, name: QuantDense(feats, dtype, name=name)
    return lambda feats, name: nn.Dense(
        feats, use_bias=False, dtype=dtype, name=name
    )


class RMSNorm(nn.Module):
    """Root-mean-square norm, f32 accumulation, learned scale."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


class _ProjToBHSD(nn.Module):
    """Q/K/V projection writing straight into ``[B, H, S, D]``.

    Param-tree-identical to ``nn.Dense(H*D, use_bias=False)`` — same
    ``kernel`` name, shape ``[d_model, H*D]``, init, and dtype policy — so
    checkpoints interchange freely with the BSHD path and the tensor-
    parallel column rule (which shards the kernel's last dim along head
    boundaries) applies unchanged. The layout change lives entirely in the
    einsum's output indexing: XLA emits one matmul whose result is laid out
    as BHSD, where reshape-then-transpose after a Dense materializes a
    ``[B,S,H,D]``-sized copy per projection per step.
    """

    num_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        features = self.num_heads * self.head_dim
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], features),
            jnp.float32,
        )
        k = kernel.astype(self.dtype).reshape(
            x.shape[-1], self.num_heads, self.head_dim
        )
        return jnp.einsum("bsm,mhd->bhsd", x.astype(self.dtype), k)


class _ProjFromBHSD(nn.Module):
    """Output projection consuming ``[B, H, S, D]`` context directly.

    Param-tree-identical to the BSHD path's ``nn.Dense(d_model)`` out_proj
    (kernel ``[H*D, d_model]``, head-major rows — the same ordering
    ``ctx.reshape(B, S, H*D)`` produces), so the tensor-parallel row rule
    applies unchanged and no ``[B,S,H,D]`` transpose precedes the matmul.
    """

    out_features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ctx: jax.Array) -> jax.Array:
        _, heads, _, head_dim = ctx.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (heads * head_dim, self.out_features), jnp.float32,
        )
        k = kernel.astype(self.dtype).reshape(heads, head_dim, self.out_features)
        return jnp.einsum("bhsd,hdm->bsm", ctx.astype(self.dtype), k)


class Indexer(nn.Module):
    """The lightning indexer's projections (``ops/sparse_attention.py``):
    from the normed hidden state, ``num_heads`` small query heads, ONE key
    head (RMSNorm'd) and one scalar weight a query head; RoPE over all of
    ``head_dim`` on queries and key. Returns ``(qI [B,S,Hi,Di], w [B,S,Hi],
    kI [B,S,Di])``."""

    num_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(
        self, x: jax.Array, positions: jax.Array
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        batch, seq, _ = x.shape
        dense = _dense_factory(False, self.dtype)
        q = dense(self.num_heads * self.head_dim, "q_proj")(x).reshape(
            batch, seq, self.num_heads, self.head_dim
        )
        k = RMSNorm(self.norm_eps, name="k_norm")(dense(self.head_dim, "k_proj")(x))
        w = dense(self.num_heads, "w_proj")(x)
        q = apply_rope(q, positions, base=self.rope_theta)
        k = apply_rope(k[:, :, None, :], positions, base=self.rope_theta)[:, :, 0]
        return q, w, k


class Attention(nn.Module):
    """Multi-head self-attention with RoPE and a pluggable attention core.

    ``decode=True`` switches to single-token autoregressive mode: K/V for
    each new token are appended to a ``cache`` collection
    (``cached_key``/``cached_value`` ``[B, max_len, Hkv, D]`` where ``Hkv``
    is ``num_kv_heads`` — fewer than ``num_heads`` under GQA — plus a
    scalar ``cache_index``), and the query attends over the filled prefix —
    O(S) per generated token instead of re-running the O(S²) full sequence.

    An ``attention_fn`` carrying ``.layout == 'bhsd'`` (e.g.
    ``ops.pallas.flash_attention_bhsd``) flips the whole module to the
    kernel-native layout: q/k/v are *projected* into ``[B, H, S, D]`` and
    the context consumed from it, so no BSHD↔BHSD copy exists anywhere in
    the layer — forward or backward (the ~5% step-time transpose tax
    measured in ``docs/PERF_ANALYSIS.md`` §8).
    """

    num_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16
    attention_fn: AttentionFn | None = None
    #: ``False`` = full-sequence training/eval forward. ``True`` = KV-cached
    #: single-token decode. ``"prefill"`` = the cache-WRITING full-sequence
    #: pass: a multi-token chunk is projected once, written into the cache
    #: buffers, and attended with the full-sequence core (flash on TPU) —
    #: O(P) sequential steps become one MXU-batched forward. Valid ONLY on a
    #: fresh (empty) cache: the chunk attends within itself, not to prior
    #: cache rows (``models.generate.prefill`` owns that contract).
    decode: bool | str = False
    #: grouped-query attention: number of shared K/V heads (None = num_heads,
    #: plain MHA). K/V are projected and CACHED at this head count — the KV
    #: cache and decode HBM reads shrink by num_heads/num_kv_heads — and the
    #: full-sequence cores receive ``repeat_kv``'d tensors (see
    #: ops.attention.repeat_kv for why that trade is per-phase correct).
    num_kv_heads: int | None = None
    #: weight-only int8 projections (``ops.quant.QuantDense``); inference
    #: only — params come from ``ops.quant.quantize_lm_params``.
    quantized: bool = False
    #: sliding-window (local) attention: each query attends its last
    #: ``window`` tokens, self included (0 = unlimited). One knob drives all
    #: three cores consistently — the full-sequence ``attention_fn`` (dense
    #: oracle or flash kernels, which skip out-of-window blocks), AND the
    #: KV-cached decode walk (which then starts at the window's first cache
    #: block: O(window) HBM reads per token however long the generation).
    #: Both SP schedules compose: Ulysses passes the window through to its
    #: full-sequence inner core; the ring statically trims its rotation
    #: schedule to the shards any query's window reaches (rotation
    #: skipping, ``parallel.ring_attention.windowed_rotations``).
    window: int = 0
    #: rotary base (``TransformerConfig.rope_theta``, or this layer's own:
    #: :class:`LayerSpec`)
    rope_theta: float = 10_000.0
    #: YaRN frequency scaling of this layer's RoPE (:attr:`LayerSpec.yarn`)
    rope_yarn: tuple[float, int, float, float, float] | None = None
    #: RMSNorm over each head's dims of q and k, before RoPE
    qk_norm: bool = False
    #: learned sparse attention: each query attends the ``topk`` keys its
    #: :class:`Indexer` (``indexer_heads`` x ``indexer_head_dim``) scores
    #: highest (0 = all). Full-sequence forward only: the KV-cached modes
    #: keep no indexer-key cache and refuse it; long contexts go through
    #: ``serving.ServingEngine``, whose paged pools hold one.
    topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    #: epsilon of the per-head q/k norms and the indexer's key norm
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array, *, causal: bool = True) -> jax.Array:
        features = self.num_heads * self.head_dim
        batch, seq, _ = x.shape
        kv_heads = self.num_kv_heads or self.num_heads
        if self.topk and (self.decode or not causal or self.quantized or self.window):
            raise NotImplementedError(
                "attention_topk > 0 (learned sparse attention) runs in the "
                "uncached causal full-sequence forward only: the flax KV "
                "cache holds no indexer keys (serve it through "
                "serving.ServingEngine), and it composes with neither a "
                "sliding window nor quantized projections"
            )
        if (self.topk or self.qk_norm) and attention_fn_layout(self.attention_fn) == "bhsd":
            raise NotImplementedError(
                "qk_norm / attention_topk support the BSHD layout only"
            )
        if self.num_heads % kv_heads:
            raise ValueError(
                f"num_kv_heads ({kv_heads}) must divide num_heads ({self.num_heads})"
            )
        rep = self.num_heads // kv_heads
        if self.quantized and attention_fn_layout(self.attention_fn) == "bhsd":
            raise ValueError(
                "quantized attention supports the BSHD path only (the BHSD "
                "kernel-native layout is a training-path optimization; "
                "quantization is inference-only)"
            )
        rope_kw = rope_kwargs(self.head_dim, self.rope_theta, self.rope_yarn)
        if not self.decode and attention_fn_layout(self.attention_fn) == "bhsd":
            proj = lambda heads, name: _ProjToBHSD(  # noqa: E731
                heads, self.head_dim, self.dtype, name=name
            )
            rope = functools.partial(
                apply_rope, positions=positions, layout="bhsd", **rope_kw
            )
            q = rope(proj(self.num_heads, "q_proj")(x))
            k = rope(proj(kv_heads, "k_proj")(x))
            v = proj(kv_heads, "v_proj")(x)
            ctx = self.attention_fn(
                q, repeat_kv(k, rep, axis=1), repeat_kv(v, rep, axis=1),
                causal=causal, **self._window_kw(),
            )  # [B, H, S, D]
            return _ProjFromBHSD(x.shape[-1], self.dtype, name="out_proj")(ctx)
        dense = _dense_factory(self.quantized, self.dtype)
        kv_shape = (batch, seq, kv_heads, self.head_dim)
        q = dense(features, "q_proj")(x).reshape(
            batch, seq, self.num_heads, self.head_dim
        )
        k = dense(kv_heads * self.head_dim, "k_proj")(x).reshape(kv_shape)
        v = dense(kv_heads * self.head_dim, "v_proj")(x).reshape(kv_shape)
        if self.qk_norm:
            q = RMSNorm(self.norm_eps, name="q_norm")(q)
            k = RMSNorm(self.norm_eps, name="k_norm")(k)
        q = apply_rope(q, positions, **rope_kw)
        k = apply_rope(k, positions, **rope_kw)
        if self.topk:
            q_idx, w_idx, k_idx = Indexer(
                self.indexer_heads, self.indexer_head_dim, self.rope_theta,
                self.dtype, self.norm_eps, name="indexer",
            )(x, positions)
            ctx = sparse_attention(q, k, v, q_idx, w_idx, k_idx, self.topk)
        elif self.decode:
            ctx = self._cached_attention(q, k, v)
        else:
            attn = self.attention_fn or dense_attention
            if attention_fn_accepts_gqa(attn):
                # GQA-native schedule (the ring): grouped K/V go straight
                # in — the repeat happens inside, after the ICI hop.
                ctx = attn(q, k, v, causal=causal, **self._window_kw())
            else:
                ctx = attn(
                    q, repeat_kv(k, rep), repeat_kv(v, rep), causal=causal,
                    **self._window_kw(),
                )
        ctx = ctx.reshape(batch, seq, features)
        # "out_proj" triggers tensor_parallel's row-parallel (input-dim) rule.
        return dense(x.shape[-1], "out_proj")(ctx)

    def _window_kw(self) -> dict:
        """``{'window': N}`` for the attention core when sliding-window is
        on — passed as a kwarg so a core that cannot honor it fails loudly
        (the ring factory raises; an unknown injected core TypeErrors)
        instead of silently attending to the full sequence. Dense, flash,
        and Ulysses all accept it."""
        return {"window": self.window} if self.window else {}

    def _cached_attention(self, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        """One decode step: append K/V to the cache, attend over the prefix.

        The cache must be initialized by an ``init(..., decode=True)`` /
        first apply with a ``[B, max_len, ...]``-shaped input establishing
        ``max_len``; decode steps then feed one token at a time (seq == 1).
        """
        batch, seq, _, head_dim = q.shape
        kv_heads = k.shape[2]  # < q heads under GQA: the cache stores Hkv
        cached_k = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((batch, seq, kv_heads, head_dim), self.dtype),
        )
        cached_v = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((batch, seq, kv_heads, head_dim), self.dtype),
        )
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if self.is_initializing():
            return jnp.zeros_like(q)
        if seq != 1 and self.decode != "prefill":
            raise ValueError(
                f"decode mode feeds one token per step, got seq={seq}; "
                "initialize the cache with the full [B, max_len] shape "
                "(multi-token cache writes need the 'prefill' twin — "
                "models.generate.prefill)"
            )
        i = index.value
        new_k = lax.dynamic_update_slice(
            cached_k.value, k.astype(self.dtype), (0, i, 0, 0)
        )
        new_v = lax.dynamic_update_slice(
            cached_v.value, v.astype(self.dtype), (0, i, 0, 0)
        )
        cached_k.value, cached_v.value = new_k, new_v
        index.value = i + seq
        if seq != 1:
            # Prefill: the chunk attends within itself — exactly the
            # training-path full-sequence attention (flash kernel capable,
            # O(seq) memory), not seq sequential cache walks. Correct only
            # when the cache was empty (i == 0, untracked here — traced);
            # the prefill twin's contract. Same GQA dispatch as the
            # non-decode path: native schedules get grouped K/V, the rest
            # get repeated.
            attn = self.attention_fn or dense_attention
            if attention_fn_accepts_gqa(attn):
                return attn(q, k, v, causal=True, **self._window_kw())
            rep = q.shape[2] // k.shape[2]
            return attn(
                q, repeat_kv(k, rep), repeat_kv(v, rep), causal=True,
                **self._window_kw(),
            )
        # decode_attention picks its schedule at trace time on the static
        # buffer length: one fused masked einsum at the HBM roofline for
        # buffers <= DECODE_DENSE_MAX (reads all rows — safe because this
        # cache zero-initializes), the blockwise prefix walk (O(i) reads
        # per token) beyond it. Measured rationale: PERF_ANALYSIS.md §9.
        return decode_attention(
            q, new_k, new_v, i, window=self.window or None
        )


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The shapes of multi-head latent attention (DeepSeek-V3's MLA), from
    :class:`TransformerConfig`: the query's low-rank bottleneck, the cached
    latent's rank, each head's dims without and with RoPE and its value
    dims, and the softmax scale."""

    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    scale: float


class LatentAttention(nn.Module):
    """Multi-head latent attention, the uncached full-sequence forward:

    ``q = W_qb RMSNorm(W_qa h)`` split per head into ``q_nope`` and ``q_pe``;
    ``[c ; k_pe] = W_kva h`` with ``c = RMSNorm(c)``, ONE ``k_pe`` shared by
    every head; ``[k_nope_i ; v_i] = W_kvb,i c``; RoPE on ``q_pe`` and
    ``k_pe``; ``score_i = scale (q_nope_i . k_nope_i + q_pe_i . k_pe)``. What
    a cache would hold is ``[c ; RoPE(k_pe)]`` a position, ``kv_rank + rope``
    values (``serving.ServingEngine`` keeps it in a latent pool). Attention
    is the expanded form, :func:`ops.latent_attention.expanded_attention`."""

    num_heads: int
    spec: LatentSpec
    dtype: Any = jnp.bfloat16
    rope_theta: float = 10_000.0
    rope_yarn: tuple[float, int, float, float, float] | None = None
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array, *, causal: bool = True) -> jax.Array:
        if not causal:
            raise NotImplementedError("latent attention is causal")
        sp, heads = self.spec, self.num_heads
        batch, seq, _ = x.shape
        dense = _dense_factory(False, self.dtype)
        rope = functools.partial(
            apply_rope, positions=positions,
            **rope_kwargs(sp.rope, self.rope_theta, self.rope_yarn),
        )
        q = dense(heads * (sp.nope + sp.rope), "q_b_proj")(
            RMSNorm(self.norm_eps, name="q_a_norm")(dense(sp.q_rank, "q_a_proj")(x))
        ).reshape(batch, seq, heads, sp.nope + sp.rope)
        c, k_pe = jnp.split(dense(sp.kv_rank + sp.rope, "kv_a_proj")(x), [sp.kv_rank], axis=-1)
        c = RMSNorm(self.norm_eps, name="kv_a_norm")(c)
        kv_b = self.param(
            "kv_b_proj",
            lambda key, shape: {"kernel": nn.initializers.lecun_normal()(key, shape, jnp.float32)},
            (sp.kv_rank, heads * (sp.nope + sp.v)),
        )["kernel"]
        q_pos = jnp.arange(seq, dtype=jnp.int32)
        ctx = expanded_attention(
            q[..., : sp.nope], rope(q[..., sp.nope :]), c, rope(k_pe[:, :, None])[:, :, 0],
            kv_b.astype(self.dtype).reshape(sp.kv_rank, heads, sp.nope + sp.v),
            scale=sp.scale, valid=(q_pos[None, :] <= q_pos[:, None])[None],
        )
        return dense(x.shape[-1], "out_proj")(ctx.reshape(batch, seq, heads * sp.v))


class SwiGLU(nn.Module):
    """Gated MLP: ``down(silu(gate(x)) * up(x))``."""

    d_ff: int
    dtype: Any = jnp.bfloat16
    quantized: bool = False  # weight-only int8 kernels (inference only)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dense = _dense_factory(self.quantized, self.dtype)
        hidden = nn.silu(dense(self.d_ff, "gate_proj")(x)) * dense(
            self.d_ff, "up_proj"
        )(x)
        return dense(x.shape[-1], "down_proj")(hidden)


class Block(nn.Module):
    """Pre-norm transformer block: x + attn(norm(x)); x + mlp(norm(x))."""

    num_heads: int
    head_dim: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    attention_fn: AttentionFn | None = None
    mlp_cls: type[nn.Module] | None = None
    decode: bool | str = False  # False | True | "prefill" (see Attention)
    num_kv_heads: int | None = None
    quantized: bool = False
    #: False = bidirectional attention (encoder stacks: ViT); True = the
    #: causal LM default.
    causal: bool = True
    #: sliding-window attention size (0 = unlimited); see Attention.window.
    window: int = 0
    #: see the fields of the same names on :class:`Attention`
    rope_theta: float = 10_000.0
    rope_yarn: tuple[float, int, float, float, float] | None = None
    qk_norm: bool = False
    topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    #: every norm's epsilon (:attr:`TransformerConfig.rms_norm_eps`)
    norm_eps: float = 1e-6
    #: multi-head latent attention in place of :class:`Attention`
    latent: LatentSpec | None = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        if self.latent is not None:
            if self.decode or self.quantized or self.attention_fn is not None:
                raise NotImplementedError(
                    "latent attention runs in the uncached forward and the "
                    "serving engine (its paged latent pool) only: no flax "
                    "KV cache, quantized projections or injected core"
                )
            attn = LatentAttention(
                self.num_heads, self.latent, self.dtype, self.rope_theta,
                self.rope_yarn, self.norm_eps, name="attn",
            )
        else:
            attn = Attention(
                self.num_heads, self.head_dim, self.dtype,
                attention_fn=self.attention_fn, decode=self.decode,
                num_kv_heads=self.num_kv_heads, quantized=self.quantized,
                window=self.window, rope_theta=self.rope_theta,
                rope_yarn=self.rope_yarn,
                qk_norm=self.qk_norm, topk=self.topk,
                indexer_heads=self.indexer_heads,
                indexer_head_dim=self.indexer_head_dim,
                norm_eps=self.norm_eps, name="attn",
            )
        x = x + attn(
            RMSNorm(self.norm_eps, name="attn_norm")(x), positions, causal=self.causal
        )
        if self.quantized:
            if self.mlp_cls is not None:
                raise ValueError(
                    "quantized inference supports the dense SwiGLU MLP only "
                    "(routed MoE kernels are not converted)"
                )
            mlp = SwiGLU(self.d_ff, self.dtype, quantized=True, name="mlp")
        else:
            mlp = (self.mlp_cls or SwiGLU)(self.d_ff, self.dtype, name="mlp")
        return x + mlp(RMSNorm(self.norm_eps, name="mlp_norm")(x))


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What may differ from layer to layer of one model: the attention's
    reach and its RoPE (``TransformerConfig.layers``)."""

    #: sliding window of this layer (0 = every earlier key)
    window: int = 0
    #: rotary base of this layer
    rope_theta: float = 10_000.0
    #: YaRN scaling of this layer's RoPE: ``(factor, original_max,
    #: beta_fast, beta_slow, attention_factor)``; None = plain RoPE
    yarn: tuple[float, int, float, float, float] | None = None


def rope_kwargs(
    head_dim: int, rope_theta: float,
    yarn: tuple[float, int, float, float, float] | None,
) -> dict[str, Any]:
    """:func:`apply_rope`'s keywords for one layer: its base, or under YaRN
    its scaled frequencies and the attention factor."""
    if yarn is None:
        return {"base": rope_theta}
    factor, original_max, beta_fast, beta_slow, attention_factor = yarn
    return {
        "inv_freq": yarn_inv_freq(
            head_dim, rope_theta, factor, original_max, beta_fast, beta_slow
        ),
        "factor": float(attention_factor),
    }


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Size knobs for :class:`TransformerLM`; ``tiny()`` is the test config.

    ``moe_experts > 0`` swaps every block's MLP (but the first
    ``first_dense_layers``) for a routed
    :class:`~deeplearning_mpi_tpu.models.moe.MoEMLP` (top-k routing; fixed
    capacity with experts sharded over the mesh ``expert`` axis, or
    ``moe_routing='dropless'``: every claim served, no capacity).
    ``kv_lora_rank > 0`` swaps every block's attention for multi-head latent
    attention (:class:`LatentAttention`).
    """

    vocab_size: int = 32_000
    num_layers: int = 12
    num_heads: int = 12
    #: grouped-query attention: K/V heads shared by groups of query heads
    #: (None = num_heads, plain MHA). Must divide num_heads. The KV cache
    #: and decode HBM traffic shrink by num_heads/num_kv_heads.
    num_kv_heads: int | None = None
    head_dim: int = 64
    d_model: int = 768
    d_ff: int = 2048
    tied_embeddings: bool = True
    # The load-balance aux-loss weight is a *trainer* knob
    # (``Trainer(aux_weight=...)``), not a model attribute: the model only
    # sows the loss (``MoEMLP``), the training loss composes it.
    moe_experts: int = 0  # 0 = dense SwiGLU MLP
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    #: 'token_choice' (GShard top-k + aux loss) or 'expert_choice' (each
    #: expert takes its top-C tokens; balanced by construction — see
    #: MoEMLP's causality caveat before using it in a causal LM).
    moe_routing: str = "token_choice"
    #: width of one expert's SwiGLU (0 = ``d_ff``). Models that publish an
    #: expert width apart from the dense width set it; ``d_ff`` then sizes
    #: the leading dense layers' MLP (nothing in an all-expert stack).
    moe_d_ff: int = 0
    #: leading layers whose MLP is the dense SwiGLU of ``d_ff`` in a model
    #: with experts (DeepSeek-V3's ``first_k_dense_replace``)
    first_dense_layers: int = 0
    #: shared experts: ONE SwiGLU of width ``moe_d_ff * moe_shared_experts``
    #: that every token runs beside its routed experts (dropless only)
    moe_shared_experts: int = 0
    #: the router's scoring: ``'softmax'`` (the top-k renormalised) or
    #: ``'sigmoid'`` (DeepSeek-V3's aux-free routing: the top-k of the
    #: scores plus a learned correction bias ``router/bias`` are chosen, and
    #: weighted by their scores alone, renormalised). Dropless only.
    moe_scoring: str = "softmax"
    #: factor on the routed experts' gates (``routed_scaling_factor``)
    moe_routed_scale: float = 1.0
    #: the expert share, as expert parallelism holds it: the router scores
    #: ``moe_router_experts`` experts (0 = ``moe_experts``, all of them held
    #: here) and this chip holds the ``moe_experts`` from
    #: ``moe_first_expert`` on; a claim on an expert held elsewhere is not
    #: computed here (``models.moe.dropless_moe``). Dropless only.
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    #: rotary base. A MODEL property like the window: every forward (train,
    #: cached decode, the serving engine) rotates with it.
    rope_theta: float = 10_000.0
    #: RMSNorm over each head's ``head_dim`` of q and k, before RoPE
    #: (learned scales ``attn/q_norm``, ``attn/k_norm``).
    qk_norm: bool = False
    #: learned sparse attention (a lightning indexer): each query attends
    #: the ``attention_topk`` keys at or before it that an indexer of
    #: ``indexer_heads`` x ``indexer_head_dim`` (one key head) scores highest;
    #: all of them while fewer exist. 0 = every key. The selection is not
    #: differentiable and no objective for the indexer is defined here, so
    #: this is a forward-only property (``ops/sparse_attention.py``).
    attention_topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    #: sliding-window (local) attention: each query attends its last N
    #: tokens (0 = unlimited). A MODEL property, not a runtime knob — train,
    #: prefill, and KV-cached decode all mask with it, so a window-trained
    #: checkpoint decodes with the same receptive field it learned.
    attention_window: int = 0
    #: layers of several kinds in one model: one :class:`LayerSpec` a layer
    #: (its window, its RoPE base and scaling). Empty = every layer as the
    #: global ``attention_window`` and ``rope_theta`` say, which then must
    #: not be set beside it. The uncached forward, the flax KV cache and the
    #: serving engine all read a layer's own through :meth:`layer_spec`.
    layers: tuple[LayerSpec, ...] = ()
    #: every RMSNorm's epsilon (the published one; 1e-6 is the default
    #: every earlier model ran with)
    rms_norm_eps: float = 1e-6
    #: multi-head latent attention (DeepSeek-V3's MLA) where
    #: ``kv_lora_rank > 0``: the query through a rank-``q_lora_rank``
    #: bottleneck, ONE cached latent of ``kv_lora_rank + qk_rope_head_dim``
    #: values a position a layer, each head's key ``qk_nope_head_dim``
    #: expanded from the latent plus ``qk_rope_head_dim`` rotated and shared,
    #: values of ``v_head_dim``. ``head_dim`` is then the query's
    #: ``qk_nope_head_dim + qk_rope_head_dim`` and RoPE (a layer's
    #: :class:`LayerSpec`) turns the rope dims alone.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: the attention's softmax scale (0 = ``head_dim ** -0.5``); a YaRN
    #: model whose scale carries ``mscale ** 2`` states it here
    softmax_scale: float = 0.0
    #: embedding lookup as a one-hot matmul instead of a gather. Forward
    #: values are identical (rows of exact 0/1 select the same f32 bits),
    #: but the *gradient* becomes a dot-general instead of a scatter-add —
    #: the classic TPU embedding trick (scatter serializes on TPU; the MXU
    #: eats the one-hot dot), and the property the explicit ZeRO-1 schedule
    #: needs for bit-equality: GSPMD reshards a scatter-add gradient by
    #: all-gathering tokens and accumulating in *global* token order, while
    #: a dot-general keeps per-rank partial sums + all-reduce — the same
    #: association the shard_map path computes (parallel/zero.py).
    onehot_embed: bool = False

    def __post_init__(self) -> None:
        routed = (
            self.first_dense_layers or self.moe_shared_experts
            or self.moe_scoring != "softmax" or self.moe_routed_scale != 1.0
            or self.moe_router_experts or self.moe_first_expert
        )
        if routed and self.moe_routing != "dropless":
            raise NotImplementedError(
                "leading dense layers, shared experts, sigmoid routing, a "
                "routed scale and an expert share are dropless_moe's "
                "(moe_routing='dropless')"
            )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_scoring {self.moe_scoring!r}")
        if self.moe_first_expert + self.moe_experts > self.moe_router_width:
            raise ValueError(
                f"experts {self.moe_first_expert}..{self.moe_first_expert + self.moe_experts - 1} "
                f"held of a router of {self.moe_router_width}"
            )
        if self.latent:
            if not self.q_lora_rank:
                raise NotImplementedError(
                    "latent attention without a query bottleneck "
                    "(q_lora_rank 0) is not implemented"
                )
            if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError(
                    f"head_dim {self.head_dim} of latent attention is the "
                    "query's qk_nope_head_dim + qk_rope_head_dim"
                )
            windows = {self.layer_spec(i).window for i in range(self.num_layers)}
            if self.attention_topk or windows != {0}:
                raise NotImplementedError(
                    "latent attention with a sliding window or a learned "
                    "selection is not implemented"
                )
        if not self.layers:
            return
        if len(self.layers) != self.num_layers:
            raise ValueError(
                f"layers describes {len(self.layers)} layers, num_layers is "
                f"{self.num_layers}"
            )
        if self.attention_window:
            raise ValueError(
                "attention_window beside layers: a layer's window is its "
                "LayerSpec's"
            )
        if self.attention_topk:
            raise NotImplementedError(
                "attention_topk > 0 with layers: a selecting layer among "
                "layers of several kinds is not implemented"
            )

    def layer_spec(self, i: int) -> LayerSpec:
        """Layer ``i``'s window and RoPE: its own where ``layers`` is
        given, else the model's global ones."""
        if self.layers:
            return self.layers[i]
        return LayerSpec(self.attention_window, self.rope_theta)

    def moe_layer(self, i: int) -> bool:
        """Whether layer ``i``'s MLP is the expert layer."""
        return self.moe_experts > 0 and i >= self.first_dense_layers

    @property
    def moe_layers(self) -> int:
        return sum(map(self.moe_layer, range(self.num_layers)))

    @property
    def moe_router_width(self) -> int:
        """Experts the router scores: all of the layer's, held here or not."""
        return self.moe_router_experts or self.moe_experts

    @property
    def expert_share(self) -> bool:
        """Whether the router scores experts held elsewhere too."""
        return self.moe_experts > 0 and self.moe_router_width != self.moe_experts

    @property
    def latent(self) -> LatentSpec | None:
        """Multi-head latent attention's shapes, or None for K/V attention."""
        if not self.kv_lora_rank:
            return None
        return LatentSpec(
            self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim,
            self.softmax_scale or self.head_dim**-0.5,
        )

    @property
    def rope_dim(self) -> int:
        """The dims RoPE turns: a latent model's rope dims, else a head's."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def mlp_width(self) -> int:
        """Width of the block's MLP: one expert's where the model publishes
        it apart from the dense width, else ``d_ff``."""
        return self.moe_d_ff if self.moe_experts and self.moe_d_ff else self.d_ff

    @staticmethod
    def tiny() -> "TransformerConfig":
        return TransformerConfig(
            vocab_size=256, num_layers=2, num_heads=4, head_dim=8,
            d_model=32, d_ff=64,
        )

    @staticmethod
    def tiny_moe(num_experts: int = 4) -> "TransformerConfig":
        return dataclasses.replace(TransformerConfig.tiny(), moe_experts=num_experts)


def draft_config(
    config: "TransformerConfig", num_layers: int, **overrides: Any
) -> "TransformerConfig":
    """A draft-model config derived from a target's: same vocab (the one
    hard requirement of speculative decoding — draft and target must share
    a tokenizer), fewer layers, any width knobs overridable. The default
    (depth-only truncation) pairs with :func:`truncate_lm_params` to make
    a zero-training "self-draft" from the target's own weights."""
    if not 1 <= num_layers <= config.num_layers:
        raise ValueError(
            f"draft num_layers must be in [1, {config.num_layers}], "
            f"got {num_layers}"
        )
    if config.moe_experts > 0:
        raise ValueError("draft models must be dense (no MoE)")
    return dataclasses.replace(config, num_layers=num_layers, **overrides)


def truncate_lm_params(params: Any, num_layers: int) -> Any:
    """Self-draft params: the target's embedding, first ``num_layers``
    blocks, final norm, and (untied) LM head, referenced — not copied —
    from the target tree.

    Layer truncation is the cheapest useful draft: early blocks carry most
    of next-token prediction for easy continuations, the tied embedding
    doubles as the draft's output head ("logit reuse" — draft and target
    argmax over the SAME output geometry, which is what makes a truncated
    draft agree with its target far more often than an independently
    initialized model of the same size), and no extra training or storage
    is needed. The exact-greedy-match verify step makes draft quality a
    throughput knob, never a correctness one. Use with
    :func:`draft_config`'s depth-only truncation — width overrides need
    independently shaped (and trained) draft weights."""
    keep = {"embed", "final_norm"} | {f"layer_{i}" for i in range(num_layers)}
    if f"layer_{num_layers - 1}" not in params:
        raise ValueError(
            f"target params hold fewer than {num_layers} layers"
        )
    if "lm_head" in params:
        keep.add("lm_head")
    return {k: params[k] for k in params if k in keep}


def _remat_block(policy: bool | str) -> type[nn.Module]:
    """Resolve a remat policy name to the (possibly wrapped) Block class."""
    if isinstance(policy, str):
        policy = policy.lower()
    if policy in (False, None, "", "none"):
        return Block
    if policy in (True, "full"):
        return nn.remat(Block)
    if policy == "dots":
        return nn.remat(
            Block, policy=jax.checkpoint_policies.checkpoint_dots
        )
    raise ValueError(
        f"unknown remat policy {policy!r} (expected False/'none', "
        "True/'full', or 'dots')"
    )


class TransformerLM(nn.Module):
    """Causal LM: token embed → N blocks → final norm → logits.

    ``remat`` wraps each block in ``jax.checkpoint`` — rematerialisation
    trades recompute FLOPs for HBM, the standard TPU memory lever for long
    sequences. ``True``/``"full"`` saves only block boundaries (backward
    re-runs each block's forward — one extra forward of block FLOPs,
    ``telemetry.flops.transformer_remat_flops``); ``"dots"`` saves matmul
    outputs and recomputes only the elementwise glue
    (``jax.checkpoint_policies.checkpoint_dots`` — near-zero extra FLOPs,
    intermediate memory); ``False``/``"none"`` saves everything.
    """

    config: TransformerConfig
    dtype: Any = jnp.bfloat16
    attention_fn: AttentionFn | None = None
    remat: bool | str = False
    mlp_cls: type[nn.Module] | None = None
    #: False | True | "prefill": KV-cached decode modes (see Attention.decode)
    decode: bool | str = False
    #: return (final-norm activations, head kernel [d, V]) instead of
    #: logits, for the chunked head+loss path (``ops.loss.chunked_lm_loss``)
    #: that never materializes [B, S, V] logits. Tied embeddings only — the
    #: untied head's Dense would have to be built-but-skipped, forking the
    #: param tree. The param tree is unchanged, so checkpoints interchange
    #: freely with the plain model.
    return_prehead: bool = False
    #: weight-only int8 projections (inference only): apply with a param
    #: tree from ``ops.quant.quantize_lm_params``. Embeddings, norms, and
    #: the tied head stay in the compute dtype.
    quantized: bool = False

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: jax.Array | None = None,
        *,
        train: bool = False,
    ) -> jax.Array:
        del train  # no dropout/batch-stats yet; accepted for trainer uniformity
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[-1], dtype=jnp.int32)[None, :], tokens.shape
            )
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=self.dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed",
        )
        if cfg.onehot_embed:
            # Same param tree, same forward bits, scatter-free backward —
            # see the config field's comment.
            onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=self.dtype)
            x = jnp.einsum(
                "bsv,vd->bsd", onehot, embed.embedding.astype(self.dtype)
            )
        else:
            x = embed(tokens)
        mlp_cls = self.mlp_cls
        if mlp_cls is None and cfg.moe_experts > 0:
            from deeplearning_mpi_tpu.models.moe import mlp_cls_from_config

            mlp_cls = mlp_cls_from_config(cfg)
        block_cls = _remat_block(self.remat)
        for i in range(cfg.num_layers):
            spec = cfg.layer_spec(i)
            # a leading dense layer of a model with experts: SwiGLU of d_ff
            dense = self.mlp_cls is None and cfg.moe_experts and not cfg.moe_layer(i)
            x = block_cls(
                cfg.num_heads, cfg.head_dim,
                cfg.d_ff if dense else cfg.mlp_width, self.dtype,
                attention_fn=self.attention_fn,
                mlp_cls=None if dense else mlp_cls,
                decode=self.decode, num_kv_heads=cfg.num_kv_heads,
                quantized=self.quantized, window=spec.window,
                rope_theta=spec.rope_theta, rope_yarn=spec.yarn,
                qk_norm=cfg.qk_norm,
                topk=cfg.attention_topk, indexer_heads=cfg.indexer_heads,
                indexer_head_dim=cfg.indexer_head_dim,
                norm_eps=cfg.rms_norm_eps, latent=cfg.latent,
                name=f"layer_{i}",
            )(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if self.return_prehead:
            if not cfg.tied_embeddings:
                raise ValueError(
                    "return_prehead requires tied_embeddings (an untied "
                    "lm_head would have to be built-but-skipped, forking "
                    "the param tree)"
                )
            return x, embed.embedding.T
        if cfg.tied_embeddings:
            logits = embed.attend(x.astype(self.dtype))
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head"
            )(x)
        return logits.astype(jnp.float32)  # loss/softmax wants f32 logits
