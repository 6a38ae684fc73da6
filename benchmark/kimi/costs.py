"""Operations and bytes a decode step of Kimi-K2.7-Code's language model
needs, from shapes and from what the step routed: the count is of the work,
whatever implements it (``benchmark/costs.py``'s rule). bf16 throughout.

What depends on the data: the latent attention reads every live latent row
of a sequence once (``kv_lora_rank + qk_rope_head_dim`` values a position a
layer) and, in its absorbed form, does ``2 H (kv_lora_rank + rope)`` plus
``2 H kv_lora_rank`` operations a position a layer (scores, then the
weighted sum of latents); of the routed experts only those the step's rows
touched among the ones held here have to be read (the program counts them,
``serve_moe_experts_touched``), and the claims that land on a held expert
are computed, which the harness does not collect: they are taken at their
expected count, the rows' claims times the held share of the router.
"""

from __future__ import annotations

from typing import Any, Iterable

BF16 = 2


def attention_params(cfg: dict[str, Any]) -> int:
    """A latent attention layer's matrices: q_a, q_b, kv_a, kv_b, out."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return d * qr + qr * heads * (nope + rope) + d * (kvr + rope) + kvr * heads * (nope + v) + heads * v * d


def expert_params(cfg: dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]  # gate, up, down


def router_params(cfg: dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["router_experts"]


def dense_mlp_params(cfg: dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_counts(cfg: dict[str, Any]) -> tuple[int, int]:
    """(dense layers, expert layers) among the layers held."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def shared_params(cfg: dict[str, Any]) -> int:
    """The matrices every step reads once whatever it routes: attention,
    the dense MLPs, the routers and shared experts of the expert layers."""
    dense, experts = layer_counts(cfg)
    per_expert_layer = router_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg)
    return cfg["num_hidden_layers"] * attention_params(cfg) + dense * dense_mlp_params(cfg) + experts * per_expert_layer


def head_params(cfg: dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict[str, Any]) -> int:
    d = cfg["hidden_size"]
    norms = 2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]  # a layer's four norm scales
    dense, experts = layer_counts(cfg)
    held = cfg["n_routed_experts"] * expert_params(cfg) + cfg["router_experts"]  # + the router's bias
    embed = d * cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return embed + head + shared_params(cfg) + experts * held + cfg["num_hidden_layers"] * norms + d


def latent_row_bytes(cfg: dict[str, Any]) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16  # one position's latent, one layer


def latent_attention_cost(cfg: dict[str, Any], contexts: Iterable[int]) -> tuple[int, int]:
    """(operations, bytes) of the absorbed latent attention's core in one
    decode step over all layers: scores over ``kv_lora_rank + rope`` and the
    weighted sum of latents, every head, every live position of every row;
    each live latent row read once."""
    rows = cfg["num_hidden_layers"] * sum(contexts)
    heads, kvr, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2 * heads * (2 * kvr + rope) * rows, rows * latent_row_bytes(cfg)


def absorb_flops(cfg: dict[str, Any]) -> int:
    """A row's query into the latent and the output out of it, one layer."""
    return 2 * cfg["num_attention_heads"] * cfg["kv_lora_rank"] * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def held_claims(cfg: dict[str, Any], rows: int) -> float:
    """Expected claims on held experts a step: the rows' claims in every
    expert layer times the held share of the router."""
    return rows * cfg["num_experts_per_tok"] * layer_counts(cfg)[1] * cfg["n_routed_experts"] / cfg["router_experts"]


def decode_step_cost(cfg: dict[str, Any], contexts: Iterable[int], touched: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step over rows whose known lengths
    are ``contexts`` and whose tokens touched ``touched`` (layer, held
    expert) pairs: every matrix outside the routed experts and the head once,
    the touched experts once, every live latent row once."""
    contexts = list(contexts)
    rows = len(contexts)
    weights = shared_params(cfg) + head_params(cfg)
    attn_flops, attn_bytes = latent_attention_cost(cfg, contexts)
    flops = 2 * weights * rows + cfg["num_hidden_layers"] * rows * absorb_flops(cfg) + attn_flops
    flops += 2 * held_claims(cfg, rows) * expert_params(cfg)
    return flops, (weights + touched * expert_params(cfg)) * BF16 + attn_bytes
