"""Mellum 2's published keys on one side, ``TransformerConfig``'s on the other."""

from __future__ import annotations

from typing import Any

from benchmark.program import compute_dtype  # noqa: F401  (the same two types)


def layer_spec(cfg: dict[str, Any], kind: str) -> Any:
    """One layer's window and RoPE from its ``layer_types`` entry."""
    from deeplearning_mpi_tpu.models.transformer import LayerSpec

    rope = cfg["rope_parameters"][kind]
    yarn = None
    if rope["rope_type"] == "yarn":
        yarn = (
            float(rope["factor"]), rope["original_max_position_embeddings"], float(rope["beta_fast"]),
            float(rope["beta_slow"]), float(rope["attention_factor"]),
        )
    return LayerSpec(cfg["sliding_window"] if kind == "sliding_attention" else 0, float(rope["rope_theta"]), yarn)


def model_config(cfg: dict[str, Any]) -> Any:
    from deeplearning_mpi_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        tied_embeddings=cfg["tie_word_embeddings"],
        moe_experts=cfg["num_experts"], moe_top_k=cfg["num_experts_per_tok"], moe_routing="dropless",
        moe_d_ff=cfg["moe_intermediate_size"],
        layers=tuple(layer_spec(cfg, kind) for kind in cfg["layer_types"]),
    )
