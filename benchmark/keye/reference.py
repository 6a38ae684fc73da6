"""The plain reference of Keye-VL-2.0's language model, serving side.

float32 with every matmul at ``highest`` precision, one sequence, a full
forward pass: no cache, no kernels, no batching, nothing imported from the
program. The layer, as ``configs/keye-vl-2.0-30b-a3b-l6.json`` states it with
every assumption: pre-norm; q, k, v projections, RMSNorm over each head's
dims of q and k, rotary embeddings in split-half form; the indexer's own
scores ``I(t, s) = sum_j w_tj relu(qI_tj . kI_s) / sqrt(Di Hi)`` with its own
``top_k`` (ties to the lower position); softmax attention under the mask that
selection gives, the same keys for every head; then the expert layer: softmax
router in float32, the ``num_experts_per_tok`` largest renormalised, every
expert run on the tokens that chose it. Attention goes a block of queries at
a time, and the experts one at a time over the tokens that chose them, padded
to ``capacity`` rows (the host reads the largest load first and picks it), so
that 58k positions fit. The vision tower is left out and M-RoPE is plain RoPE
for text (the three position components are equal).

``lower`` rounds both operands of every matmul as ``benchmark/reference.py``
does; that is the control. ``faults`` plants the errors the limits have to
catch (``benchmark/keye/tools/faults.py`` reads them).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import _freeze, _mm, _rms, _rope, _rounder

FAULTS = ("dense_attention", "half_topk", "drop_one_expert", "gates_not_renormalised")


def _select(scores: jax.Array, q_pos: jax.Array, topk: int) -> jax.Array:
    """``scores [Q, L]`` -> bool ``[Q, L]``: the ``topk`` highest keys at or
    before each query (all of them while fewer exist)."""
    seen = jnp.arange(scores.shape[1])[None, :] <= q_pos[:, None]
    if scores.shape[1] <= topk:
        return seen
    _, ids = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], ids].set(True)
    return picked & seen


def _attention(h: jax.Array, lp: Any, cfg: dict[str, Any], rnd: Callable, block: int, faults: frozenset) -> jax.Array:
    """``h [S, d]`` (normed) -> context ``[S, H * D]``."""
    seq = h.shape[0]
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    topk = sa["topk"] // 2 if "half_topk" in faults else sa["topk"]
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    at, ix = lp["attn"], lp["attn"]["indexer"]
    pos = jnp.arange(seq)
    q = _mm("sd,df->sf", h, f32(at["q_proj"]["kernel"]), rnd).reshape(seq, heads, dim)
    k = _mm("sd,df->sf", h, f32(at["k_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    v = _mm("sd,df->sf", h, f32(at["v_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    q = _rope(_rms(q, f32(at["q_norm"]["scale"]), eps), pos, theta).reshape(seq, kv_heads, heads // kv_heads, dim)
    k = _rope(_rms(k, f32(at["k_norm"]["scale"]), eps), pos, theta)
    qi = _rope(_mm("sd,df->sf", h, f32(ix["q_proj"]["kernel"]), rnd).reshape(seq, hi, di), pos, theta)
    ki = _rms(_mm("sd,df->sf", h, f32(ix["k_proj"]["kernel"]), rnd), f32(ix["k_norm"]["scale"]), eps)
    ki = _rope(ki[:, None, :], pos, theta)[:, 0]
    wi = _mm("sd,df->sf", h, f32(ix["w_proj"]["kernel"]), rnd)

    def one(args: tuple[jax.Array, ...]) -> jax.Array:
        qb, qib, wib, q_pos = args  # [Q, Hkv, rep, D], [Q, Hi, Di], [Q, Hi], [Q]
        if "dense_attention" in faults:
            mask = jnp.arange(seq)[None, :] <= q_pos[:, None]
        else:
            dots = jax.nn.relu(_mm("qhd,kd->qhk", qib, ki, rnd))
            mask = _select(_mm("qhk,qh->qk", dots, wib, rnd) * (di**-0.5 * hi**-0.5), q_pos, topk)

        def head(kv: tuple[jax.Array, ...]) -> jax.Array:
            qh, kh, vh = kv  # [Q, rep, D], [S, D], [S, D]
            s = _mm("qrd,kd->rqk", qh, kh, rnd) * dim**-0.5
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return _mm("rqk,kd->qrd", p, vh, rnd)

        out = jax.lax.map(head, (qb.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        return out.transpose(1, 0, 2, 3)  # [Q, Hkv, rep, D]

    n = seq // block
    parts = jax.lax.map(one, (
        q.reshape(n, block, kv_heads, heads // kv_heads, dim), qi.reshape(n, block, hi, di),
        wi.reshape(n, block, hi), pos.reshape(n, block),
    ))
    return parts.reshape(seq, heads * dim)


def _route(h: jax.Array, lp: Any, cfg: dict[str, Any], rnd: Callable, faults: frozenset) -> jax.Array:
    """Gate of every (token, expert) pair, ``[S, E]`` float32: 0 where the
    token did not choose the expert."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("sd,de->se", h, lp["mlp"]["router"]["kernel"].astype(jnp.float32), rnd), axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"] and "gates_not_renormalised" not in faults:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    if "drop_one_expert" in faults:
        top = top.at[:, -1].set(0.0)
    return jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], ids].set(top)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower", "block", "faults"))
def _attend_and_route(x, lp, cfg_key, lower, block, faults):
    cfg, rnd = dict(cfg_key), _rounder(lower)
    cfg["sa_config"] = dict(cfg["sa_config"])
    h = _rms(x, lp["attn_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    ctx = _attention(h, lp, cfg, rnd, block, faults)
    x = x + _mm("sf,fd->sd", ctx, lp["attn"]["out_proj"]["kernel"].astype(jnp.float32), rnd)
    h = _rms(x, lp["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    gates = _route(h, lp, cfg, rnd, faults)
    return x, h, gates, jnp.max(jnp.sum(gates > 0, axis=0))


@functools.partial(jax.jit, static_argnames=("capacity", "lower"))
def _experts(x, h, gates, mlp, capacity, lower):
    """``x + sum_e gate_e * expert_e(h)``, one expert at a time over the (at
    most ``capacity``) tokens that chose it."""
    rnd = _rounder(lower)

    def one(out: jax.Array, args: tuple[jax.Array, ...]) -> tuple[jax.Array, None]:
        g, wg, wu, wd = args  # [S], [d, f], [d, f], [f, d]
        rows = jnp.nonzero(g > 0, size=capacity, fill_value=0)[0]
        weight = jnp.where(jnp.arange(capacity) < jnp.sum(g > 0), g[rows], 0.0)
        hs = h[rows]
        hidden = jax.nn.silu(_mm("sd,df->sf", hs, wg.astype(jnp.float32), rnd)) * _mm("sd,df->sf", hs, wu.astype(jnp.float32), rnd)
        y = _mm("sf,fd->sd", hidden, wd.astype(jnp.float32), rnd)
        return out.at[rows].add(weight[:, None] * y), None

    out, _ = jax.lax.scan(one, x, (gates.T, mlp["experts_gate"], mlp["experts_up"], mlp["experts_down"]))
    return out


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, rows, norm_scale, head, eps, lower):
    h = _rms(x[rows], norm_scale.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, head.astype(jnp.float32), _rounder(lower))


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _index_keys(x, lp, eps, theta):
    ix = lp["attn"]["indexer"]
    h = _rms(x, lp["attn_norm"]["scale"].astype(jnp.float32), eps)
    ki = _rms(_mm("sd,df->sf", h, ix["k_proj"]["kernel"].astype(jnp.float32), _rounder(None)), ix["k_norm"]["scale"].astype(jnp.float32), eps)
    return _rope(ki[:, None, :], jnp.arange(x.shape[0]), theta)[:, 0]


def index_keys(cfg: dict[str, Any], params: Any, tokens: np.ndarray) -> jax.Array:
    """The first layer's indexer key of every position, ``[len(tokens), Di]``
    float32: it depends on the token and its position alone, so what a
    server's cache holds there can be compared whatever was selected."""
    x = params["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    return _index_keys(x, params["layer_0"], cfg["rms_norm_eps"], float(cfg["rope_theta"]))


def _key(cfg: dict[str, Any]) -> tuple:
    return (*_freeze(cfg), ("sa_config", _freeze(cfg["sa_config"])))


def serve_logits(
    cfg: dict[str, Any], params: Any, tokens: np.ndarray, rows: np.ndarray, *,
    lower: str | None = None, faults: frozenset = frozenset(), block: int = 128, pad_to: int = 4096,
) -> jax.Array:
    """Logits ``[len(rows), V]`` of one sequence at the positions ``rows``,
    from a full forward pass over ``tokens`` (padded at the end, which a
    causal model does not see). ``params`` hold the served values (bf16)."""
    n = len(tokens)
    pad_to = min(pad_to, -(-n // block) * block)
    ids = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
    ids[:n] = tokens
    key = _key(cfg)
    x = params["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layer_{i}"]
        x, h, gates, load = _attend_and_route(x, lp, key, lower, min(block, len(ids)), faults)
        capacity = min(len(ids), 1 << max(int(load) - 1, 0).bit_length())  # few sizes, so few programs
        x = _experts(x, h, gates, lp["mlp"], capacity, lower)
    head = params["embed"]["embedding"].T if cfg["tie_word_embeddings"] else params["lm_head"]["kernel"]
    held = np.full((-(-len(rows) // 128) * 128,), rows[-1], np.int32)
    held[: len(rows)] = rows
    return _head(x, jnp.asarray(held), params["final_norm"]["scale"], head, cfg["rms_norm_eps"], lower)[: len(rows)]
