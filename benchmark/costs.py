"""Operations and bytes the work needs, from shapes alone, and the table of
peaks. The count is of the work, whatever implements it: recomputation, padded
tiles and copies a program adds are not counted, so a share over 100% means
the count is too high or the time leaves out work (run.py warns, never clips).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Iterable

PEAKS = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())


def peak(device_kind: str) -> dict[str, Any]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}: add it to peaks.json with its source")
    return PEAKS[device_kind]


def layer_matmul_params(cfg: dict[str, Any]) -> int:
    d, heads, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attention = 2 * d * heads * hd + 2 * d * kv * hd  # q, out; k, v
    return attention + 3 * d * cfg["intermediate_size"]  # gate, up, down


def layer_params(cfg: dict[str, Any]) -> int:
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]  # two norm scales


def head_params(cfg: dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict[str, Any]) -> int:
    embed = cfg["hidden_size"] * cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return embed + head + cfg["num_hidden_layers"] * layer_params(cfg) + cfg["hidden_size"]


def attended_pairs(start: int, n: int, window: int) -> int:
    """Query-key pairs a causal mask with a sliding window lets in, for the
    ``n`` queries at positions ``start .. start+n-1`` (window 0 = none)."""
    last = start + n - 1
    if not window or last < window:
        return (start + 1 + last + 1) * n // 2
    ramp = max(0, window - 1 - start)  # queries that still see every earlier key
    return (start + 1 + start + ramp) * ramp // 2 + (n - ramp) * window


def attention_flops(cfg: dict[str, Any], pairs: int) -> int:
    """QK^T and PV of one layer's forward pass: 2 + 2 operations a pair a
    head-dimension element."""
    return 4 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def train_step_flops(cfg: dict[str, Any], rows: int, seq: int) -> int:
    """Forward and backward of one step (backward = twice forward); the
    embedding lookup is no matmul; nothing recomputed is counted."""
    dense = 2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)) * rows * seq
    attn = cfg["num_hidden_layers"] * rows * attention_flops(cfg, attended_pairs(0, seq, cfg["sliding_window"]))
    return 3 * (dense + attn)


def flash_train_cost(cfg: dict[str, Any], rows: int, seq: int) -> tuple[int, int]:
    """(operations, bytes) of attention forward + dq + dkv over all layers of
    one step: 3x the forward's masked-in pairs; q, o, do, dq once at H heads,
    k, v, dk, dv once at the KV heads, forward reads and writes and backward
    reads and writes, in bf16."""
    flops = 3 * cfg["num_hidden_layers"] * rows * attention_flops(cfg, attended_pairs(0, seq, cfg["sliding_window"]))
    q = rows * seq * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = rows * seq * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    fwd = 2 * q + 2 * kv  # read q k v, write o
    bwd = 4 * q + 4 * kv  # read q o do k v, write dq dk dv
    return flops, cfg["num_hidden_layers"] * (fwd + bwd)


def decode_step_cost(cfg: dict[str, Any], contexts: Iterable[int]) -> tuple[int, int]:
    """(operations, bytes) of one decode step over rows whose known lengths are
    ``contexts``: every matmul weight read once in bf16, each row's live KV
    rows (under the window) read once."""
    contexts = list(contexts)
    weights = cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)
    window = cfg["sliding_window"]
    live = sum(min(c, window) if window else c for c in contexts)
    kv_row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2  # k and v, bf16
    flops = 2 * weights * len(contexts) + cfg["num_hidden_layers"] * attention_flops(cfg, live)
    return flops, 2 * weights + cfg["num_hidden_layers"] * live * kv_row


def prefill_chunk_flops(cfg: dict[str, Any], start: int, n: int) -> int:
    """One prefill chunk of ``n`` prompt tokens from position ``start``: the
    layers on every token, the head on the last."""
    dense = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * n + 2 * head_params(cfg)
    return dense + cfg["num_hidden_layers"] * attention_flops(cfg, attended_pairs(start, n, cfg["sliding_window"]))


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over peak
    operations a second and bytes over peak bytes a second."""
    p = peak(device_kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
