"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is handed the
tree (in the type it trains or serves in) and the plain reference makes the
same tree again from the seed, so the reference takes nothing the program made.
The tree's names are ``TransformerLM``'s own.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp


def shapes(cfg: dict[str, Any]) -> dict[str, Any]:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = {
        "attn_norm": {"scale": (d,)},
        "attn": {
            "q_proj": {"kernel": (d, hq)}, "k_proj": {"kernel": (d, hkv)},
            "v_proj": {"kernel": (d, hkv)}, "out_proj": {"kernel": (hq, d)},
        },
        "mlp_norm": {"scale": (d,)},
        "mlp": {
            "gate_proj": {"kernel": (d, ff)}, "up_proj": {"kernel": (d, ff)},
            "down_proj": {"kernel": (ff, d)},
        },
    }
    tree: dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = {"kernel": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = layer
    return tree


def flat_shapes(cfg: dict[str, Any]) -> tuple[list[tuple[str, tuple[int, ...], float]], Any]:
    """``(name, shape, std)`` of every leaf in ``jax.tree`` order, and the
    tree's structure. A norm scale has std 0: it is 1 plus a tenth of the noise,
    near 1 and not at 1, so that every leaf's gradient differs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for path, shape in flat:
        names = [p.key for p in path]
        std = 0.0 if names[-1] == "scale" else cfg["initializer_range"] if names[-1] == "embedding" else 1.0 / math.sqrt(shape[0])
        out.append(("/".join(names), shape, std))
    return out, treedef


def leaf(seed: jax.Array, index: Any, shape: tuple[int, ...], std: float) -> jax.Array:
    """Leaf number ``index`` of the seed's tree, float32."""
    root = jax.random.wrap_key_data(jnp.asarray(seed, jnp.uint32), impl="threefry2x32")
    noise = jax.random.normal(jax.random.fold_in(root, index), shape, jnp.float32)
    return std * noise if std else 1.0 + 0.1 * noise


def build(cfg: dict[str, Any], seed: jax.Array, dtype: Any = jnp.float32) -> dict[str, Any]:
    """Traceable: the whole tree from a uint32 seed pair, cast to ``dtype``."""
    flat, treedef = flat_shapes(cfg)
    leaves = [leaf(seed, i, shape, std).astype(dtype) for i, (_, shape, std) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnames=("std",))
def _change(value: jax.Array, seed: jax.Array, index: jax.Array, std: float) -> jax.Array:
    return jnp.sqrt(jnp.sum(jnp.square(value.astype(jnp.float32) - leaf(seed, index, value.shape, std))))


def change_norms(cfg: dict[str, Any], seed: jax.Array, params: Any) -> list[float]:
    """Per-leaf norm of ``params`` minus the seed's own weights, one leaf at a
    time so that no second copy of the tree is ever on the device."""
    flat, _ = flat_shapes(cfg)
    return [
        float(_change(value, seed, jnp.asarray(i, jnp.uint32), std))
        for i, ((_, _, std), value) in enumerate(zip(flat, jax.tree.leaves(params)))
    ]


def seed_words(seed: int) -> jax.Array:
    """``--seed`` (any whole number up to a little over 2**31) as key data."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32)


def leaf_names(cfg: dict[str, Any]) -> list[str]:
    return [name for name, _, _ in flat_shapes(cfg)[0]]
