"""The plain reference: Mistral's published layer in straightforward jax.numpy.

float32 with every matmul at ``highest`` precision, no kernels, no cache, no
batching; attention in blocks of queries so that it fits beside nothing else.
It imports nothing of the program and takes nothing the program made: weights
come from ``weights.build`` and the seed. Follows the published description
(pre-norm, RMSNorm, rotary embeddings in split-half form, grouped-query
attention under a causal sliding window, SwiGLU, untied head); the one
departure is the norm's epsilon, which the configuration file states as run.

``lower`` computes the same thing with both operands of every matmul rounded
to a lower precision under per-tensor scaling (straight through: the backward
pass sees the rounded operands and an unrounded cotangent). That is the control: put in the
program's place it has to come out as not correct.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOWER = {"float8_e4m3fn": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}


def _rounder(lower: str | None) -> Callable[[jax.Array], jax.Array]:
    """Round a matmul operand to ``lower`` the way a low-precision path would:
    scaled so that the tensor's largest magnitude is the type's largest, rounded,
    scaled back (per-tensor dynamic scaling); straight through for gradients."""
    if lower is None:
        return lambda a: a
    dtype = LOWER[lower]
    top = float(jnp.finfo(dtype).max)

    def rnd(a: jax.Array) -> jax.Array:
        scale = jnp.max(jnp.abs(a)) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        return a + jax.lax.stop_gradient((a / scale).astype(dtype).astype(a.dtype) * scale - a)

    return rnd


def _mm(eq: str, a: jax.Array, b: jax.Array, rnd: Callable) -> jax.Array:
    return jnp.einsum(eq, rnd(a), rnd(b), precision=HIGHEST)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int, rnd: Callable, block: int) -> jax.Array:
    """``q [S, Hkv, rep, D]``, ``k, v [S, Hkv, D]`` -> ``[S, Hkv, rep, D]``:
    softmax over the keys at or before each query and fewer than ``window``
    back, one block of queries and one KV head at a time."""
    seq, kv_heads, rep, dim = q.shape
    block = min(block, seq)
    assert seq % block == 0, (seq, block)
    span = min(seq, (window or seq) + block)
    scale = dim ** -0.5

    @jax.checkpoint
    def one(qb: jax.Array, kh: jax.Array, vh: jax.Array, q_start: jax.Array) -> jax.Array:
        k_start = jnp.clip(q_start + block - span, 0, seq - span)
        ks = jax.lax.dynamic_slice_in_dim(kh, k_start, span, axis=0)
        vs = jax.lax.dynamic_slice_in_dim(vh, k_start, span, axis=0)
        q_pos = q_start + jnp.arange(block)[:, None]
        k_pos = k_start + jnp.arange(span)[None, :]
        seen = k_pos <= q_pos
        if window:
            seen &= q_pos - k_pos < window
        scores = _mm("qrd,kd->rqk", qb, ks, rnd) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return _mm("rqk,kd->qrd", probs, vs, rnd)

    def head(args: tuple[jax.Array, jax.Array, jax.Array]) -> jax.Array:
        qh, kh, vh = args  # [S, rep, D], [S, D], [S, D]
        blocks = qh.reshape(seq // block, block, rep, dim)
        starts = jnp.arange(seq // block) * block
        out = jax.lax.map(lambda a: one(a[0], kh, vh, a[1]), (blocks, starts))
        return out.reshape(seq, rep, dim)

    out = jax.lax.map(head, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3)


def layer(x: jax.Array, lp: Any, cfg: dict[str, Any], rnd: Callable, block: int = 1024) -> jax.Array:
    """One block on one sequence ``x [S, d]`` (positions 0..S-1)."""
    seq = x.shape[0]
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pos = jnp.arange(seq)
    f32 = lambda w: w.astype(jnp.float32)
    h = _rms(x, f32(lp["attn_norm"]["scale"]), cfg["rms_norm_eps"])
    q = _mm("sd,df->sf", h, f32(lp["attn"]["q_proj"]["kernel"]), rnd).reshape(seq, heads, dim)
    k = _mm("sd,df->sf", h, f32(lp["attn"]["k_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    v = _mm("sd,df->sf", h, f32(lp["attn"]["v_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    q = _rope(q, pos, cfg["rope_theta"]).reshape(seq, kv_heads, heads // kv_heads, dim)
    k = _rope(k, pos, cfg["rope_theta"])
    ctx = _attention(q, k, v, cfg["sliding_window"], rnd, block).reshape(seq, heads * dim)
    x = x + _mm("sf,fd->sd", ctx, f32(lp["attn"]["out_proj"]["kernel"]), rnd)
    h = _rms(x, f32(lp["mlp_norm"]["scale"]), cfg["rms_norm_eps"])
    gate = _mm("sd,df->sf", h, f32(lp["mlp"]["gate_proj"]["kernel"]), rnd)
    up = _mm("sd,df->sf", h, f32(lp["mlp"]["up_proj"]["kernel"]), rnd)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, f32(lp["mlp"]["down_proj"]["kernel"]), rnd)


def _head_kernel(params: Any, cfg: dict[str, Any]) -> jax.Array:
    if cfg["tie_word_embeddings"]:
        return params["embed"]["embedding"].astype(jnp.float32).T
    return params["lm_head"]["kernel"].astype(jnp.float32)


# -- training: loss, gradients, Adam ----------------------------------------

def _row_nll(params: Any, tokens: jax.Array, weight: jax.Array, cfg: dict[str, Any], rnd: Callable, chunk: int) -> jax.Array:
    """Sum over one row of weight x next-token negative log-likelihood."""
    seq = tokens.shape[0]
    x = params["embed"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg=cfg, rnd=rnd))(x, params[f"layer_{i}"])
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    head = _head_kernel(params, cfg)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last position predicts nothing
    weight = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)])
    chunk = min(chunk, seq)

    @jax.checkpoint
    def piece(args: tuple[jax.Array, jax.Array, jax.Array]) -> jax.Array:
        xc, tc, wc = args
        logits = _mm("sd,dv->sv", xc, head, rnd)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * wc)

    parts = jax.lax.map(piece, (x.reshape(seq // chunk, chunk, -1), targets.reshape(-1, chunk), weight.reshape(-1, chunk)))
    return jnp.sum(parts)


def row_loss(params: Any, tokens: jax.Array, weights: jax.Array, total_weight: jax.Array, cfg: dict[str, Any], lower: str | None = None, chunk: int = 1024) -> jax.Array:
    """One row's share of the weighted mean next-token loss: ``tokens [S]``,
    ``weights [S-1]`` 1 where a position counts (all of them, in a sound run),
    over the whole batch's ``total_weight``. The rows' shares add up to the loss."""
    return _row_nll(params, tokens, weights, cfg, _rounder(lower), chunk) / total_weight


def leaf_norms(tree: Any) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])


leaf_norms_jit = jax.jit(leaf_norms)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _row_loss_and_grads(params, tokens, weights, total_weight, cfg_key, lower):
    cfg = dict(cfg_key)
    return jax.value_and_grad(lambda p: row_loss(p, tokens, weights, total_weight, cfg, lower))(params)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))


def loss_and_grads(params: Any, tokens: np.ndarray, weights: np.ndarray, cfg: dict[str, Any], lower: str | None, devices: list[Any]) -> tuple[float, Any]:
    """Loss and gradients over ``tokens [R, S]``, a row at a time: row ``r`` on
    ``devices[r % n]`` (each with its own copy of the parameters), the
    gradients added up on the last device and brought back to the first."""
    home, n = devices[0], len(devices)
    total = jnp.asarray(weights.sum(), jnp.float32)
    copies = [params] + [jax.device_put(params, d) for d in devices[1:]]
    parts = [
        _row_loss_and_grads(copies[r % n], jax.device_put(tokens[r], devices[r % n]), jax.device_put(weights[r], devices[r % n]),
                            jax.device_put(total, devices[r % n]), _freeze(cfg), lower)
        for r in range(tokens.shape[0])
    ]
    del copies
    loss, grads = 0.0, None
    for part_loss, part in reversed(parts):  # the last device's own first
        loss += float(part_loss)
        part = jax.device_put(part, devices[-1])
        grads = part if grads is None else _add(grads, part)
    del parts
    return loss, jax.device_put(grads, home)


@functools.partial(jax.jit, static_argnames=("hp_key",), donate_argnums=(0, 1, 2))
def _adam(params, mu, nu, grads, count, hp_key):
    """clip by global norm, then Adam, as optax chains them. Returns the new
    params and moments and the per-leaf norms of the gradient as Adam gets it."""
    hp = dict(hp_key)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = hp["clip_norm"]
    grads = jax.tree.map(lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    got = leaf_norms(grads)
    b1, b2 = hp["adam_b1"], hp["adam_b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    t = count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, n: p - hp["learning_rate"] * (m / (1 - b1 ** t)) / (jnp.sqrt(n / (1 - b2 ** t)) + hp["adam_eps"]),
        params, mu, nu,
    )
    return params, mu, nu, got


def _freeze(d: dict[str, Any]) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items() if isinstance(v, (int, float, str, bool))))


def follow_training(
    cfg: dict[str, Any], seed: int, batches: list[np.ndarray], *,
    lower: str | None = None, weights: list[np.ndarray] | None = None, devices: list[Any] | None = None,
) -> dict[str, Any]:
    """Train from the seed's weights over ``batches`` (each ``[R, S]`` token
    ids) and report what the program is held to: each step's loss, the
    per-leaf norm of the first gradient as Adam gets it (and raw, for the rule
    on idle leaves), and the per-leaf norm of the parameters' change."""
    from benchmark import weights as weights_mod

    hp = _freeze({k: cfg["train"][k] for k in ("learning_rate", "clip_norm", "adam_b1", "adam_b2", "adam_eps")})
    devices = list(devices or jax.devices()[:1])
    words = jax.device_put(weights_mod.seed_words(seed), devices[0])
    params = jax.jit(lambda s: weights_mod.build(cfg, s, jnp.float32))(words)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out: dict[str, Any] = {"loss": []}
    for step, tokens in enumerate(batches, start=1):
        w = np.ones((tokens.shape[0], tokens.shape[1] - 1), np.float32) if weights is None else weights[step - 1]
        loss, grads = loss_and_grads(params, np.asarray(tokens), w, cfg, lower, devices)
        if step == 1:
            out["first_grad_raw"] = np.asarray(leaf_norms(grads))
        params, mu, nu, got = _adam(params, mu, nu, grads, jax.device_put(jnp.asarray(step), devices[0]), hp)
        if step == 1:
            out["first_grad"] = np.asarray(got)
        out["loss"].append(loss)
    out["change"] = np.asarray(weights_mod.change_norms(cfg, words, params))
    out["names"] = weights_mod.leaf_names(cfg)
    del params, mu, nu
    return out


# -- serving: logits of one sequence ----------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _serve_layer(x, lp, cfg_key, lower):
    return layer(x, lp, dict(cfg_key), _rounder(lower), block=512)


@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _serve_head(x, rows, norm_scale, head, cfg_key, lower):
    cfg = dict(cfg_key)
    h = _rms(x[rows], norm_scale.astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm("sd,dv->sv", h, head.astype(jnp.float32), _rounder(lower))


def serve_logits(cfg: dict[str, Any], params: Any, tokens: np.ndarray, rows: np.ndarray, *, lower: str | None = None, pad_to: int = 512) -> jax.Array:
    """Logits ``[len(rows), V]`` of one sequence at the positions ``rows``,
    from a full forward pass over ``tokens`` (padded at the end, which a
    causal model does not see). ``params`` hold the served values (bf16)."""
    n = len(tokens)
    padded = -(-n // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    key = _freeze(cfg)
    x = params["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _serve_layer(x, params[f"layer_{i}"], key, lower)
    head = params["embed"]["embedding"].T if cfg["tie_word_embeddings"] else params["lm_head"]["kernel"]
    held = np.full((-(-len(rows) // 128) * 128,), rows[-1], np.int32)  # few shapes, so few programs
    held[: len(rows)] = rows
    return _serve_head(x, jnp.asarray(held), params["final_norm"]["scale"], head, key, lower)[: len(rows)]
