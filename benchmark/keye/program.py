"""Keye-VL-2.0's published keys on one side, ``TransformerConfig``'s on the other."""

from __future__ import annotations

from typing import Any

from benchmark.program import compute_dtype  # noqa: F401  (the same two types)


def model_config(cfg: dict[str, Any]) -> Any:
    from deeplearning_mpi_tpu.models import TransformerConfig

    sa = cfg["sa_config"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        tied_embeddings=cfg["tie_word_embeddings"], rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        moe_experts=cfg["num_experts"], moe_top_k=cfg["num_experts_per_tok"], moe_routing="dropless",
        moe_d_ff=cfg["moe_intermediate_size"], attention_topk=sa["topk"],
        indexer_heads=sa["indexer_num_heads"], indexer_head_dim=sa["indexer_head_dim"],
    )
