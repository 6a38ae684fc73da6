"""Operations and bytes a decode step of Mellum 2's decoder needs, from shapes
and from what the step routed: the count is of the work, whatever implements
it (``benchmark/costs.py``'s rule). bf16 throughout.

What depends on the data: a ``full_attention`` layer reads every live K/V row
of a sequence, a ``sliding_attention`` layer the last ``sliding_window`` of
them at most; only the experts a step's rows touched have to be read, and the
program counts those (``serve_moe_experts_touched``).
"""

from __future__ import annotations

from typing import Any, Iterable

BF16 = 2


def attention_params(cfg: dict[str, Any]) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * kv * hd  # q, out; k, v


def expert_params(cfg: dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]  # gate, up, down


def shared_layer_params(cfg: dict[str, Any]) -> int:
    """A layer's matmul weights outside its experts: every step reads them once."""
    return attention_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]  # + router


def layer_params(cfg: dict[str, Any]) -> int:
    return shared_layer_params(cfg) + cfg["num_experts"] * expert_params(cfg) + 2 * cfg["hidden_size"]  # + two norm scales


def head_params(cfg: dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict[str, Any]) -> int:
    embed = cfg["hidden_size"] * cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return embed + head + cfg["num_hidden_layers"] * layer_params(cfg) + cfg["hidden_size"]


def kv_row_bytes(cfg: dict[str, Any]) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16  # one position's K and V, one layer


def layer_counts(cfg: dict[str, Any]) -> tuple[int, int]:
    """(full layers, window layers) among the layers held."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    full = sum(kind == "full_attention" for kind in kinds)
    return full, len(kinds) - full


def attended_rows(cfg: dict[str, Any], contexts: Iterable[int]) -> int:
    """K/V rows one decode step has to read, summed over layers: a row of
    known length ``c`` attends ``c`` positions in a full layer and
    ``min(c, sliding_window)`` in a window layer."""
    contexts = list(contexts)
    full, window = layer_counts(cfg)
    return full * sum(contexts) + window * sum(min(c, cfg["sliding_window"]) for c in contexts)


def attention_cost(cfg: dict[str, Any], contexts: Iterable[int]) -> tuple[int, int]:
    """(operations, bytes) of attention in one decode step over all layers:
    QK^T and PV, 4 operations a head element a row attended, and each such
    row's K and V read once."""
    rows = attended_rows(cfg, contexts)
    return 4 * rows * cfg["num_attention_heads"] * cfg["head_dim"], rows * kv_row_bytes(cfg)


def moe_cost(cfg: dict[str, Any], rows: int, touched: float) -> tuple[float, float]:
    """(operations, bytes) of the grouped products of one decode step over all
    layers: ``rows`` tokens through ``num_experts_per_tok`` experts each, and
    the weights of the ``touched`` (layer, expert) pairs read once."""
    flops = 2 * cfg["num_hidden_layers"] * rows * cfg["num_experts_per_tok"] * expert_params(cfg)
    return flops, touched * expert_params(cfg) * BF16


def decode_step_cost(cfg: dict[str, Any], contexts: Iterable[int], touched: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step over rows whose known lengths
    are ``contexts`` and whose tokens touched ``touched`` (layer, expert)
    pairs: the weights outside the experts and the head once, the touched
    experts once, the K/V rows each layer kind attends."""
    contexts = list(contexts)
    shared = cfg["num_hidden_layers"] * shared_layer_params(cfg) + head_params(cfg)
    attn_flops, attn_bytes = attention_cost(cfg, contexts)
    moe_flops, moe_bytes = moe_cost(cfg, len(contexts), touched)
    return 2 * shared * len(contexts) + attn_flops + moe_flops, shared * BF16 + attn_bytes + moe_bytes
