"""Kimi-K2.7-Code's published keys on one side, ``TransformerConfig``'s on the other."""

from __future__ import annotations

import math
from typing import Any

from benchmark.program import compute_dtype  # noqa: F401  (the same two types)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: dict[str, Any]) -> float:
    """``mscale(factor, mscale_all_dim)^2 / sqrt(q head dim)``."""
    rs = cfg["rope_scaling"]
    return yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def model_config(cfg: dict[str, Any]) -> Any:
    from deeplearning_mpi_tpu.models.transformer import LayerSpec, TransformerConfig

    if (cfg["n_group"], cfg["topk_group"], cfg["topk_method"]) != (1, 1, "noaux_tc") or not cfg["norm_topk_prob"]:
        raise NotImplementedError("group-limited routing, or top-k gates left unnormalised")
    rs = cfg["rope_scaling"]
    yarn = (
        float(rs["factor"]), rs["original_max_position_embeddings"], float(rs["beta_fast"]), float(rs["beta_slow"]),
        yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"]),
    )
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], tied_embeddings=cfg["tie_word_embeddings"],
        moe_experts=cfg["n_routed_experts"], moe_top_k=cfg["num_experts_per_tok"], moe_routing="dropless",
        moe_d_ff=cfg["moe_intermediate_size"], first_dense_layers=cfg["first_k_dense_replace"],
        moe_shared_experts=cfg["n_shared_experts"], moe_scoring=cfg["scoring_func"],
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_router_experts=cfg["router_experts"], moe_first_expert=cfg["experts_first"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], softmax_scale=softmax_scale(cfg),
        layers=tuple(LayerSpec(0, float(cfg["rope_theta"]), yarn) for _ in range(cfg["num_hidden_layers"])),
    )
