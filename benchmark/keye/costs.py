"""Operations and bytes a decode step of Keye-VL-2.0's language model needs,
from shapes and from what the step selected and routed: the count is of the
work, whatever implements it (``benchmark/costs.py``'s rule). bf16 throughout.

What depends on the data: each row attends ``min(context, topk)`` keys but its
indexer scores all ``context`` of them; only the experts a step's rows
touched have to be read, and the program counts those
(``serve_moe_experts_touched``).
"""

from __future__ import annotations

from typing import Any, Iterable

BF16 = 2


def attention_params(cfg: dict[str, Any]) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * kv * hd  # q, out; k, v


def indexer_params(cfg: dict[str, Any]) -> int:
    sa, d = cfg["sa_config"], cfg["hidden_size"]
    return d * sa["indexer_num_heads"] * sa["indexer_head_dim"] + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"]


def expert_params(cfg: dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]  # gate, up, down


def shared_layer_params(cfg: dict[str, Any]) -> int:
    """A layer's matmul weights outside its experts: every step reads them once."""
    return attention_params(cfg) + indexer_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]  # + router


def head_params(cfg: dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict[str, Any]) -> int:
    norms = 2 * cfg["hidden_size"] + 2 * cfg["head_dim"] + cfg["sa_config"]["indexer_head_dim"]
    layer = shared_layer_params(cfg) + cfg["num_experts"] * expert_params(cfg) + norms
    embed = cfg["hidden_size"] * cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return embed + head + cfg["num_hidden_layers"] * layer + cfg["hidden_size"]


def kv_row_bytes(cfg: dict[str, Any]) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16  # one position's K and V, one layer


def index_row_bytes(cfg: dict[str, Any]) -> int:
    return cfg["sa_config"]["indexer_head_dim"] * BF16


def select_cost(cfg: dict[str, Any], contexts: Iterable[int]) -> tuple[int, int]:
    """(operations, bytes) of scoring, selecting and attending in one decode
    step over all layers: the indexer's dot over every live key (2 a head
    element; ReLU, the head weights and the top-k itself are not matmul work
    and are not counted), attention over the kept keys (4 a head element), and
    the reads both need: every live indexer key, the kept K/V rows."""
    contexts = list(contexts)
    sa, layers = cfg["sa_config"], cfg["num_hidden_layers"]
    live = sum(contexts)
    kept = sum(min(c, sa["topk"]) for c in contexts)
    flops = 2 * live * sa["indexer_num_heads"] * sa["indexer_head_dim"] + 4 * kept * cfg["num_attention_heads"] * cfg["head_dim"]
    return layers * flops, layers * (live * index_row_bytes(cfg) + kept * kv_row_bytes(cfg))


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
    experts once, the selection's reads."""
    contexts = list(contexts)
    shared = cfg["num_hidden_layers"] * shared_layer_params(cfg) + head_params(cfg)
    sel_flops, sel_bytes = select_cost(cfg, contexts)
    moe_flops, moe_bytes = moe_cost(cfg, len(contexts), touched)
    return 2 * shared * len(contexts) + sel_flops + moe_flops, shared * BF16 + sel_bytes + moe_bytes
