"""The one place that turns a configuration file's keys into the program's own
settings: the published names on one side, ``TransformerConfig``'s on the other."""

from __future__ import annotations

from typing import Any


def model_config(cfg: dict[str, Any]) -> Any:
    from deeplearning_mpi_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        tied_embeddings=cfg["tie_word_embeddings"], attention_window=cfg["sliding_window"],
    )


def compute_dtype(cfg: dict[str, Any]) -> Any:
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
