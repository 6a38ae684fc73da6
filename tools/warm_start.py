"""What a serving cell's engine pays at start-up, program by program.

Builds the engine of a benchmark cell as
``benchmark/drivers/serve_sessions.py`` builds it (the configuration's
weights from ``--seed``, the engine group of the configuration and the
traffic), runs ``ServingEngine.warmup()`` under the persistent compile cache
(``compiler.cache.configure``: ``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``) and prints one JSON line a program
(``lower_seconds``, ``compile_seconds``, ``cache_hit``) and one ``warmup``
line: the wall time of the warm-up (what the benchmark's ``compile_s``
reads) and the ``serve_decode_kernel_calls`` gauge. Run it twice with the
same cache directory: the second start is the warm one. A Pallas kernel's
lowered program carries the Python call stack it was traced from, so these
entries are not the benchmark's own.

Usage: python tools/warm_start.py --workload kimi-serve-long [--seed N]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="kimi-serve-long")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax

    from benchmark.drivers.serve_sessions import modules
    from benchmark.manifest import Manifest
    from deeplearning_mpi_tpu.compiler import cache
    from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine
    from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

    cache_dir = cache.configure()
    manifest = Manifest()
    cell = manifest.cells[args.workload]
    cfg = manifest.config(cell["config"])
    merged = {**cfg["engine"], **manifest.traffic(cell["traffic"]).get("engine", {})}
    program, weights, _ = modules(cfg)
    dtype = program.compute_dtype(cfg)
    params = jax.jit(lambda words: weights.build(cfg, words, dtype))(weights.seed_words(args.seed))
    jax.block_until_ready(params)
    registry = MetricsRegistry()
    engine = ServingEngine(
        program.model_config(cfg), params,
        EngineConfig(**{k: v for k, v in merged.items() if k != "why"}),
        dtype=dtype, registry=registry,
    )
    t0 = time.perf_counter()
    programs = engine.warmup()
    warmup_s = time.perf_counter() - t0
    for name, prog in programs.items():
        print(json.dumps({
            "program": name, "lower_seconds": round(prog.lower_seconds, 3),
            "compile_seconds": round(prog.compile_seconds, 3), "cache_hit": prog.cache_hit,
        }))
    snap = registry.snapshot()
    print(json.dumps({
        "warmup": args.workload, "warmup_s": round(warmup_s, 3), "programs": len(programs),
        "serve_decode_kernel_calls": snap.get("serve_decode_kernel_calls"),
        "cache_dir": str(cache_dir), "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
