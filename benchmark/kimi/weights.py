"""Kimi-K2.7-Code's weight tree from ``--seed``: the names ``TransformerLM.init``
gives it (the leading dense layers' MLP ``gate_proj``/``up_proj``/``down_proj``,
the expert layers' ``router`` with its correction ``bias``, the held experts
and the ``shared`` expert), the values drawn as ``benchmark/weights.py`` draws
them (embedding std ``initializer_range``, every matrix ``1/sqrt(fan_in)``,
norm scales near 1) and the router's bias with std ``BIAS_STD``."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from benchmark.weights import leaf, seed_words  # noqa: F401

BIAS_STD = 0.1


def shapes(cfg: dict[str, Any]) -> dict[str, Any]:
    d, heads, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, e, shared = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    swiglu = lambda width: {  # noqa: E731
        "gate_proj": {"kernel": (d, width)}, "up_proj": {"kernel": (d, width)}, "down_proj": {"kernel": (width, d)},
    }
    attn = {
        "q_a_proj": {"kernel": (d, qr)}, "q_a_norm": {"scale": (qr,)}, "q_b_proj": {"kernel": (qr, heads * (nope + rope))},
        "kv_a_proj": {"kernel": (d, kvr + rope)}, "kv_a_norm": {"scale": (kvr,)}, "kv_b_proj": {"kernel": (kvr, heads * (nope + vd))},
        "out_proj": {"kernel": (heads * vd, d)},
    }
    experts = {
        "router": {"kernel": (d, cfg["router_experts"]), "bias": (cfg["router_experts"],)},
        "experts_gate": (e, d, f), "experts_up": (e, d, f), "experts_down": (e, f, d), "shared": swiglu(shared),
    }
    tree: dict[str, Any] = {"embed": {"embedding": (v, d)}, "final_norm": {"scale": (d,)}}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = {"kernel": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        mlp = swiglu(cfg["intermediate_size"]) if i < cfg["first_k_dense_replace"] else experts
        tree[f"layer_{i}"] = {"attn_norm": {"scale": (d,)}, "attn": attn, "mlp_norm": {"scale": (d,)}, "mlp": mlp}
    return tree


def flat_shapes(cfg: dict[str, Any]) -> tuple[list[tuple[str, tuple[int, ...], float]], Any]:
    """``(name, shape, std)`` of every leaf in ``jax.tree`` order, and the
    tree's structure; std 0 marks a norm scale (1 plus a tenth of the noise).
    A stack of experts ``[E, in, out]`` has fan-in ``in``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for path, shape in flat:
        name = path[-1].key
        std = {"scale": 0.0, "bias": BIAS_STD, "embedding": cfg["initializer_range"]}.get(name, None)
        out.append(("/".join(p.key for p in path), shape, 1.0 / math.sqrt(shape[-2]) if std is None else std))
    return out, treedef


def build(cfg: dict[str, Any], seed: jax.Array, dtype: Any = jnp.float32) -> dict[str, Any]:
    """Traceable: the whole tree from a uint32 seed pair, cast to ``dtype``."""
    flat, treedef = flat_shapes(cfg)
    leaves = [leaf(seed, i, shape, std).astype(dtype) for i, (_, shape, std) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)
