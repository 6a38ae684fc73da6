"""The plain reference of Mellum 2's decoder, serving side.

float32 with every matmul at ``highest`` precision, one sequence, a full
forward pass: no cache, no kernels, no batching, nothing imported from the
program. The layer, as ``configs/mellum2-12b-a2.5b-l8.json`` states it with
every assumption: pre-norm; q, k, v projections (no bias, no QK-norm); rotary
embeddings in split-half form over all of a head's dims, each layer kind with
its own frequencies: a ``sliding_attention`` layer turns dimension ``j`` at
``f_j = theta^(-2j/d)`` and attends the last ``sliding_window`` positions, self
included; a ``full_attention`` layer attends every earlier position under
YaRN, written out in :func:`yarn` from ``transformers``'
``_compute_yarn_parameters``; grouped-query softmax attention; then the expert
layer: softmax router in float32, the ``num_experts_per_tok`` largest
renormalised, every expert run on the tokens that chose it. Attention goes a
block of queries at a time (``benchmark/reference.py``'s, which reads only the
keys a window lets in) and the experts one at a time over the tokens that
chose them (``benchmark/keye/reference.py``'s), so that 58k positions fit. The
MTP head the model card names has no key in the config and is left out.

``lower`` rounds both operands of every matmul as ``benchmark/reference.py``
does; that is the control. ``faults`` plants the errors the limit has to
catch (``benchmark/mellum/tools/faults.py`` reads them).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.keye.reference import _experts, _head, _route
from benchmark.reference import _attention, _freeze, _mm, _rms, _rounder

FAULTS = ("no_window", "window_short_by_one", "no_yarn", "no_attention_factor", "gates_not_renormalised", "drop_one_expert")


def yarn(dim: int, theta: float, factor: float, original: int, beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``inv_freq`` ``[dim // 2]``: dimension ``j`` makes
    ``original * f_j / 2 pi`` turns over the original context; those that
    make more than ``beta_fast`` keep ``f_j``, those under ``beta_slow`` get
    ``f_j / factor``, and between the two a linear ramp over ``j``."""
    j = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dim)

    def turns_at(turns: float) -> float:
        return dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))

    low, high = max(math.floor(turns_at(beta_fast)), 0), min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((j - low) / (high - low if high != low else 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def layer_rope(cfg: dict[str, Any], kind: str, faults: frozenset) -> tuple[np.ndarray, float]:
    """(``inv_freq [D/2]``, the factor on cos and sin) of one layer kind."""
    rope, dim = cfg["rope_parameters"][kind], cfg["head_dim"]
    theta = float(rope["rope_theta"])
    plain = theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    if rope["rope_type"] != "yarn":
        return plain, 1.0
    scaled = yarn(dim, theta, rope["factor"], rope["original_max_position_embeddings"], rope["beta_fast"], rope["beta_slow"])
    return plain if "no_yarn" in faults else scaled, 1.0 if "no_attention_factor" in faults else float(rope["attention_factor"])


def _rope(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray, factor: float) -> jax.Array:
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer_window(cfg: dict[str, Any], kind: str, faults: frozenset) -> int:
    if kind != "sliding_attention" or "no_window" in faults:
        return 0
    return cfg["sliding_window"] - ("window_short_by_one" in faults)


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "lower", "block", "faults"))
def _attend_and_route(x, lp, cfg_key, kind, lower, block, faults):
    cfg, rnd = _unkey(cfg_key), _rounder(lower)
    seq = x.shape[0]
    heads, kv_heads, dim, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    inv_freq, factor = layer_rope(cfg, kind, faults)
    pos = jnp.arange(seq)
    h = _rms(x, f32(lp["attn_norm"]["scale"]), eps)
    q = _mm("sd,df->sf", h, f32(lp["attn"]["q_proj"]["kernel"]), rnd).reshape(seq, heads, dim)
    k = _mm("sd,df->sf", h, f32(lp["attn"]["k_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    v = _mm("sd,df->sf", h, f32(lp["attn"]["v_proj"]["kernel"]), rnd).reshape(seq, kv_heads, dim)
    q = _rope(q, pos, inv_freq, factor).reshape(seq, kv_heads, heads // kv_heads, dim)
    k = _rope(k, pos, inv_freq, factor)
    ctx = _attention(q, k, v, layer_window(cfg, kind, faults), rnd, block).reshape(seq, heads * dim)
    x = x + _mm("sf,fd->sd", ctx, f32(lp["attn"]["out_proj"]["kernel"]), rnd)
    h = _rms(x, f32(lp["mlp_norm"]["scale"]), eps)
    gates = _route(h, lp, cfg, rnd, faults)
    return x, h, gates, jnp.max(jnp.sum(gates > 0, axis=0))


def _key(cfg: dict[str, Any]) -> tuple:
    """The configuration as a static argument: its numbers, and the two RoPE groups."""
    return (*_freeze(cfg), ("rope_parameters", tuple((kind, _freeze(rope)) for kind, rope in sorted(cfg["rope_parameters"].items()))))


def _unkey(cfg_key: tuple) -> dict[str, Any]:
    cfg = dict(cfg_key)
    cfg["rope_parameters"] = {kind: dict(rope) for kind, rope in cfg["rope_parameters"]}
    return cfg


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
    for i, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        lp = params[f"layer_{i}"]
        x, h, gates, load = _attend_and_route(x, lp, key, kind, lower, min(block, len(ids)), faults)
        capacity = min(len(ids), 1 << max(int(load) - 1, 0).bit_length())  # few sizes, so few programs
        x = _experts(x, h, gates, lp["mlp"], capacity, lower)
    head = params["embed"]["embedding"].T if cfg["tie_word_embeddings"] else params["lm_head"]["kernel"]
    held = np.full((-(-len(rows) // 128) * 128,), rows[-1], np.int32)
    held[: len(rows)] = rows
    return _head(x, jnp.asarray(held), params["final_norm"]["scale"], head, cfg["rms_norm_eps"], lower)[: len(rows)]
