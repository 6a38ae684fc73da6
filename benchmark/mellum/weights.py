"""Mellum 2's weight tree from ``--seed``: the names ``TransformerLM.init``
gives it, the values drawn as ``benchmark/weights.py`` draws them (embedding
std ``initializer_range``, every matrix ``1/sqrt(fan_in)``, norm scales near 1)."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from benchmark.weights import leaf, seed_words  # noqa: F401


def shapes(cfg: dict[str, Any]) -> dict[str, Any]:
    d, f, v, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["vocab_size"], cfg["num_experts"]
    hq, hkv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = {
        "attn_norm": {"scale": (d,)},
        "attn": {
            "q_proj": {"kernel": (d, hq)}, "k_proj": {"kernel": (d, hkv)},
            "v_proj": {"kernel": (d, hkv)}, "out_proj": {"kernel": (hq, d)},
        },
        "mlp_norm": {"scale": (d,)},
        "mlp": {
            "router": {"kernel": (d, e)},
            "experts_gate": (e, d, f), "experts_up": (e, d, f), "experts_down": (e, f, d),
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
    tree's structure; std 0 marks a norm scale (1 plus a tenth of the noise).
    A stack of experts ``[E, in, out]`` has fan-in ``in``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for path, shape in flat:
        names = [p.key for p in path]
        std = 0.0 if names[-1] == "scale" else cfg["initializer_range"] if names[-1] == "embedding" else 1.0 / math.sqrt(shape[-2])
        out.append(("/".join(names), shape, std))
    return out, treedef


def build(cfg: dict[str, Any], seed: jax.Array, dtype: Any = jnp.float32) -> dict[str, Any]:
    """Traceable: the whole tree from a uint32 seed pair, cast to ``dtype``."""
    flat, treedef = flat_shapes(cfg)
    leaves = [leaf(seed, i, shape, std).astype(dtype) for i, (_, shape, std) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)
