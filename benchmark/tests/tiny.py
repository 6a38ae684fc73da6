"""A copy of the benchmark's data files at test sizes, for CPU runs of the
whole harness: same manifest, cells, metrics and drivers; tiny widths, short
rows, a fast schedule. Nothing here is timed."""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any

from benchmark.manifest import ROOT

MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 256, "sliding_window": 24,
}


def make_root(tmp: Path, *, limits: dict[str, dict[str, float]] | None = None) -> Path:
    """``tmp`` as a data root: BENCHMARK.json and the data directories copied,
    every configuration and traffic file cut to test size."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    data = tmp / "benchmark"
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, data / sub)
    for path in (data / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(MODEL)
        cfg["num_hidden_layers"] = 2
        if "train" in cfg:
            cfg["train"]["attention"] = "dense"
        if "engine" in cfg:
            cfg["engine"].update(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=8, prefill_chunk=16, max_queue=64)
        path.write_text(json.dumps(cfg))
    for path in (data / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        if tr["driver"] == "train":
            tr.update(seq_len=64, trace_seconds=0.5)
        else:
            tr.update(
                rate_per_s=12.0, lead_seconds=0.5, drain_limit_seconds=30.0, trace_seconds=0.5,
                prompt_tokens={"median": 16, "sigma": 0.6, "min": 4, "max": 40},
                output_tokens={"median": 8, "sigma": 0.5, "min": 3, "max": 20},
            )
        path.write_text(json.dumps(tr))
    for cell, values in (limits or {}).items():
        path = data / "cells" / f"{cell}.json"
        doc = json.loads(path.read_text())
        doc["limits"].update(values)
        path.write_text(json.dumps(doc))
    return tmp


def cpu_chips(cell: dict[str, Any]) -> list[Any]:
    """In place of the harness's look for a TPU: the CPU's devices."""
    import jax

    from deeplearning_mpi_tpu.compiler import cache

    cache.configure()
    return jax.devices("cpu")[: cell["chips"]]
