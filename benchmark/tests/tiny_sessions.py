"""``tiny.make_root`` with the session cells cut to test size too: short
prompts, a small pool, and Keye-VL-2.0's language model at toy widths with a
top-k small enough to bind. At toy widths a bf16 rounding flips an expert or
a kept key every few tokens and the flip moves a logit as far as a planted
fault does, so the toy Keye is served in float32: there the served tokens are
the reference's own and the gap says whether the harness lines them up."""

from __future__ import annotations

import json
from pathlib import Path

from benchmark.tests import tiny

KEYE = {
    "torch_dtype": "float32",
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2, "num_local_experts": 8,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 16},
}
ENGINE = {"max_slots": 4, "num_blocks": 1024, "block_size": 8, "max_blocks_per_seq": 256, "prefill_chunk": 16, "max_queue": 16}
PROMPTS = [40, 52, 61]


def make_root(tmp: Path, *, limits: dict[str, dict[str, float]] | None = None) -> Path:
    root = tiny.make_root(tmp, limits=limits)
    data = root / "benchmark"
    for path in (data / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        if cfg.get("modules") == "keye":
            cfg.update(KEYE)
        cfg.get("engine", {}).update(ENGINE)
        path.write_text(json.dumps(cfg))
    for path in (data / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        if tr["driver"] == "serve_sessions":
            tr.update(prompt_tokens=PROMPTS, lead_after_first_tokens_s=0.2, lead_limit_seconds=60.0, trace_seconds=0.5)
            if "probe_after_close" in tr:  # 10 + 5 positions: under the toy top-k of 16 from first token to last
                tr["probe_after_close"] = {"prompt_tokens": 10, "new_tokens": 5}
            tr.pop("engine", None)
            path.write_text(json.dumps(tr))
    return root
