"""The plain reference of Kimi-K2.7-Code's language model, serving side.

float32 with every matmul at ``highest`` precision, one sequence, a full
forward pass: no cache, no kernels, no batching, nothing imported from the
program. The layer, as ``configs/kimi-k2.7-code-l5.json`` states it with
every assumption: pre-norm; multi-head latent attention written out per head
in its EXPANDED form (``q = W_qb RMSNorm(W_qa h)`` split into ``q_nope`` and
``q_pe``; ``[c ; k_pe] = W_kva h``, ``c`` normed; ``[k_nope_i ; v_i] =
W_kvb,i c``; RoPE in split-half form on ``q_pe`` and the one shared ``k_pe``
under YaRN, written out in :func:`benchmark.mellum.reference.yarn`; softmax of
``s (q_nope_i . k_nope_i + q_pe_i . k_pe)`` over every earlier position, ``s
= mscale^2 / sqrt(192)``); then layer 0's dense SwiGLU, or the expert layer:
sigmoid scores in float32 over all ``router_experts``, the top
``num_experts_per_tok`` of scores plus the correction bias chosen, weighted by
``routed_scaling_factor`` times their scores renormalised, the chosen experts
that THIS chip holds (``experts_first`` on) run on the tokens that chose them
(``benchmark/keye/reference.py``'s), and the shared expert on every token. The
sequence goes through a layer in blocks of tokens, and attention a head at a
time, a block of queries against each block of keys at or before it with
the softmax carried across the key blocks (its running maximum and sum, then
one division), so that 70k positions fit beside the weights and the blocks
wholly in a query block's future are not computed.

``lower`` rounds both operands of every matmul as ``benchmark/reference.py``
does; that is the control. ``faults`` plants the errors the limits have to
catch (``benchmark/kimi/tools/faults.py`` reads them).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.keye.reference import _experts, _head
from benchmark.mellum.reference import _rope, yarn
from benchmark.reference import _freeze, _mm, _rms, _rounder

FAULTS = ("no_mscale", "bias_in_weights", "no_routed_scale", "no_shared_expert", "latent_not_normed", "k_pe_not_rotated")
ATTENTION_FAULTS = frozenset({"no_mscale", "latent_not_normed", "k_pe_not_rotated"})
TOKENS = 2048  # positions a block, when a layer goes a block of tokens at a time, and in attention


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_freqs(cfg: dict[str, Any]) -> tuple[np.ndarray, float]:
    """(``inv_freq [rope / 2]``, the factor on cos and sin) of every layer."""
    rs = cfg["rope_scaling"]
    inv = yarn(cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"], rs["beta_slow"])
    return inv, mscale(rs["factor"], rs["mscale"]) / mscale(rs["factor"], rs["mscale_all_dim"])


def scale(cfg: dict[str, Any], faults: frozenset) -> float:
    rs = cfg["rope_scaling"]
    m = 1.0 if "no_mscale" in faults else mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _blocks(fn: Callable, *arrays: jax.Array) -> jax.Array:
    """``fn`` over blocks of ``TOKENS`` positions of ``arrays`` (all ``[S, ...]``)."""
    seq = arrays[0].shape[0]
    n = math.gcd(TOKENS, seq)
    out = jax.lax.map(lambda a: fn(*a), tuple(a.reshape((seq // n, n) + a.shape[1:]) for a in arrays))
    return out.reshape((seq,) + out.shape[2:])


def _project(x: jax.Array, lp: Any, cfg: dict[str, Any], rnd: Callable, faults: frozenset) -> tuple[jax.Array, jax.Array]:
    """``(qa [S, q_lora_rank]`` normed, ``latent [S, kv_lora_rank + rope]``
    with ``c`` normed and ``k_pe`` rotated) of every position."""
    at, eps, kvr = lp["attn"], cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    inv_freq, factor = rope_freqs(cfg)

    def one(xb: jax.Array, pos: jax.Array) -> jax.Array:
        h = _rms(xb, f32(lp["attn_norm"]["scale"]), eps)
        qa = _rms(_mm("sd,dr->sr", h, f32(at["q_a_proj"]["kernel"]), rnd), f32(at["q_a_norm"]["scale"]), eps)
        kv = _mm("sd,dr->sr", h, f32(at["kv_a_proj"]["kernel"]), rnd)
        c, k_pe = kv[:, :kvr], kv[:, kvr:]
        if "latent_not_normed" not in faults:
            c = _rms(c, f32(at["kv_a_norm"]["scale"]), eps)
        if "k_pe_not_rotated" not in faults:
            k_pe = _rope(k_pe[:, None], pos, inv_freq, factor)[:, 0]
        return jnp.concatenate([qa, c, k_pe], axis=-1)

    both = _blocks(one, x, jnp.arange(x.shape[0]))
    return both[:, : cfg["q_lora_rank"]], both[:, cfg["q_lora_rank"]:]


def _attention(x, qa, latent, lp, cfg, rnd, faults):
    """``x`` plus the attention's output: a head at a time, a block of
    queries at a time against the blocks of keys up to its own, causally
    masked."""
    heads, nope, rope, vd = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kvr, seq = cfg["kv_lora_rank"], x.shape[0]
    at = lp["attn"]
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    inv_freq, factor = rope_freqs(cfg)
    s = scale(cfg, faults)
    c, k_pe = latent[:, :kvr], latent[:, kvr:]
    w_qb = f32(at["q_b_proj"]["kernel"]).reshape(-1, heads, nope + rope).transpose(1, 0, 2)
    w_kvb = f32(at["kv_b_proj"]["kernel"]).reshape(kvr, heads, nope + vd).transpose(1, 0, 2)
    w_o = f32(at["out_proj"]["kernel"]).reshape(heads, vd, -1)
    pos = jnp.arange(seq)
    n = math.gcd(TOKENS, seq)

    def head(out: jax.Array, w: tuple[jax.Array, ...]) -> tuple[jax.Array, None]:
        wq, wkv, wo = w
        q = _mm("sr,rd->sd", qa, wq, rnd)
        q = jnp.concatenate([q[:, :nope], _rope(q[:, None, nope:], pos, inv_freq, factor)[:, 0]], axis=-1)
        kv = _mm("sc,cd->sd", c, wkv, rnd)
        k, v = jnp.concatenate([kv[:, :nope], k_pe], axis=-1), kv[:, nope:]

        def block(args: tuple[jax.Array, jax.Array]) -> jax.Array:
            qb, i = args
            q_pos = i * n + jnp.arange(n)

            def keys(j: jax.Array, carry: tuple[jax.Array, ...]) -> tuple[jax.Array, ...]:
                top, total, acc = carry
                kb, vb = (jax.lax.dynamic_slice_in_dim(a, j * n, n) for a in (k, v))
                scores = _mm("qd,kd->qk", qb, kb, rnd) * s
                scores = jnp.where(j * n + jnp.arange(n)[None, :] <= q_pos[:, None], scores, -jnp.inf)
                new = jnp.maximum(top, scores.max(axis=-1))
                probs, carried = jnp.exp(scores - new[:, None]), jnp.exp(top - new)
                return new, total * carried + probs.sum(axis=-1), acc * carried[:, None] + _mm("qk,kd->qd", probs, vb, rnd)

            start = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros((n, vd)))
            _, total, acc = jax.lax.fori_loop(0, i + 1, keys, start)
            return acc / total[:, None]

        ctx = jax.lax.map(block, (q.reshape(seq // n, n, -1), jnp.arange(seq // n))).reshape(seq, vd)
        return out + _mm("sd,dm->sm", ctx, wo, rnd), None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), (w_qb, w_kvb, w_o))
    return x + out


def _swiglu(h: jax.Array, mlp: Any, rnd: Callable) -> jax.Array:
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    gate = _mm("sd,df->sf", h, f32(mlp["gate_proj"]["kernel"]), rnd)
    up = _mm("sd,df->sf", h, f32(mlp["up_proj"]["kernel"]), rnd)
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, f32(mlp["down_proj"]["kernel"]), rnd)


def _route(h: jax.Array, mlp: Any, cfg: dict[str, Any], rnd: Callable, faults: frozenset) -> jax.Array:
    """Gate of every (token, held expert) pair, ``[S, held]`` float32: 0
    where the token did not choose the expert."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, mlp["router"]["kernel"].astype(jnp.float32), rnd))
    chosen = scores + mlp["router"]["bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(chosen, k)
    top = jnp.take_along_axis(chosen if "bias_in_weights" in faults else scores, ids, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    if "no_routed_scale" not in faults:
        top = top * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], ids].set(top)
    first = cfg["experts_first"]
    return gates[:, first : first + cfg["n_routed_experts"]]


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower", "faults"))
def _attend(x, lp, cfg_key, lower, faults):
    cfg, rnd = dict(cfg_key), _rounder(lower)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    qa, latent = _project(x, lp, cfg, rnd, faults)
    return _attention(x, qa, latent, lp, cfg, rnd, faults)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _dense(x, lp, cfg_key, lower):
    cfg, rnd = dict(cfg_key), _rounder(lower)
    return _blocks(lambda xb: xb + _swiglu(_rms(xb, lp["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"]), lp["mlp"], rnd), x)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower", "faults"))
def _route_all(x, lp, cfg_key, lower, faults):
    """``(x + the shared expert's output, h, gates [S, held], the most
    tokens a held expert takes)``."""
    cfg, rnd = dict(cfg_key), _rounder(lower)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    h = _blocks(lambda xb: _rms(xb, lp["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"]), x)
    gates = _blocks(lambda hb: _route(hb, lp["mlp"], cfg, rnd, faults), h)
    if "no_shared_expert" not in faults:
        x = x + _blocks(lambda hb: _swiglu(hb, lp["mlp"]["shared"], rnd), h)
    return x, h, gates, jnp.max(jnp.sum(gates > 0, axis=0))


def _key(cfg: dict[str, Any]) -> tuple:
    return (*_freeze(cfg), ("rope_scaling", _freeze(cfg["rope_scaling"])))


def serve_logits(
    cfg: dict[str, Any], params: Any, tokens: np.ndarray, rows: np.ndarray, *,
    lower: str | None = None, faults: frozenset = frozenset(), pad_to: int = 4096,
) -> jax.Array:
    """Logits ``[len(rows), V]`` of one sequence at the positions ``rows``,
    from a full forward pass over ``tokens`` (padded at the end, which a
    causal model does not see). ``params`` hold the served values (bf16)."""
    n = len(tokens)
    pad_to = min(pad_to, -(-n // 512) * 512)
    ids = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
    ids[:n] = tokens
    key = _key(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            lp = params[f"layer_{i}"]
            x = _attend(x, lp, key, lower, faults & ATTENTION_FAULTS)  # a program a way that changes it, no more
            if i < cfg["first_k_dense_replace"]:
                x = _dense(x, lp, key, lower)
                continue
            x, h, gates, load = _route_all(x, lp, key, lower, faults - ATTENTION_FAULTS)
            capacity = min(len(ids), 1 << max(int(load) - 1, 0).bit_length())  # few sizes, so few programs
            x = _experts(x, h, gates, lp["mlp"], capacity, lower)
        head = params["embed"]["embedding"].T if cfg["tie_word_embeddings"] else params["lm_head"]["kernel"]
        held = np.full((-(-len(rows) // 128) * 128,), rows[-1], np.int32)
        held[: len(rows)] = rows
        return _head(x, jnp.asarray(held), params["final_norm"]["scale"], head, cfg["rms_norm_eps"], lower)[: len(rows)]
