#!/usr/bin/env python
"""Offline Pallas kernel autotuner — search block sizes, write a tuning DB.

    # tune flash attention + the decode schedule for serving shapes,
    # persist the winners (the kernels consult this DB at call-site)
    python tools/autotune.py --db tuned.json \
        --attn_shape 4x4096x8x64 --decode_shape 8x2048x8x64

    # tune the whole TRAIN STEP schedule (remat policy, grad-accum
    # chunking, donation, overlapped-vs-GSPMD ZeRO-1) for a training shape
    # on this machine's mesh; Trainer consumes it via --tuned_step
    python tools/autotune.py --db tuned.json --step 8x2048

    # consume it
    python -m deeplearning_mpi_tpu.cli.serve_lm --tuning_db tuned.json ...
    DMT_TUNING_DB=tuned.json python -m deeplearning_mpi_tpu.cli.train_lm ...

    python tools/autotune.py --selftest   # CI gate (`make tune-smoke`)

Shapes are ``BxSxHxD`` for attention (the BSHD call layout),
``BxLxHkvxD`` for the decode KV buffer, and ``BxS`` for step tuning.
Every candidate is verified against its oracle before it may win — kernel
candidates against the dense math, step candidates against the untuned
step's per-step LOSS TRAJECTORY — so the DB can only ever make things
faster, never different (``deeplearning_mpi_tpu/compiler/autotune.py``;
docs/COMPILATION.md; docs/PERF_ANALYSIS.md for the step-tuning workflow).

``--selftest`` runs the full acceptance loop on tiny CPU shapes: tune both
kernels, round-trip the DB, check tuned kernels match the defaults
numerically, then AOT-warm two serving engines against one persistent
compile cache — the second engine must see cache HITS and serve its first
request with ZERO compiles (the ``serve_compile_total`` trace counter).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

# Runnable as `python tools/autotune.py` from anywhere — the package root
# is this file's grandparent, not necessarily on sys.path.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _parse_shape(
    spec: str, what: str, ndims: int = 4, example: str = "4x4096x8x64"
) -> tuple[int, ...]:
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
        if len(dims) != ndims or any(d <= 0 for d in dims):
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"bad {what} '{spec}': want {ndims} positive dims like {example}"
        )
    return dims


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dmt-autotune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--db", default="tuned.json",
                        help="tuning DB to create or update (existing "
                        "entries for other keys are kept)")
    parser.add_argument("--attn_shape", action="append", default=[],
                        metavar="BxSxHxD",
                        help="flash-attention shape to tune (repeatable)")
    parser.add_argument("--decode_shape", action="append", default=[],
                        metavar="BxLxHkvxD",
                        help="decode KV-buffer shape to tune (repeatable)")
    parser.add_argument("--spec_k", type=int, default=None,
                        metavar="DRAFT_LAYERS",
                        help="search the speculative proposal depth k "
                        "end-to-end for a DRAFT_LAYERS-layer self-draft: "
                        "races real serving engines per candidate k and "
                        "records the winner with its measured acceptance "
                        "rate")
    parser.add_argument("--heads", type=int, default=None,
                        help="query heads for decode tuning (default: Hkv "
                        "— no GQA)")
    parser.add_argument("--dtype", default="float32",
                        choices=("float32", "bfloat16"))
    parser.add_argument("--blocks", default=None,
                        help="comma-separated candidate block sizes "
                        "(default: the module's search space)")
    parser.add_argument("--repeats", type=int, default=3,
                        help="timing repeats per candidate (median wins)")
    parser.add_argument("--step", action="append", default=[],
                        metavar="BxS",
                        help="LM train-step shape (global batch x seq) to "
                        "tune the whole-step schedule for (repeatable)")
    parser.add_argument("--step_model", default="lm",
                        help="model family for --step entries")
    parser.add_argument("--grad_accums", default="1,2",
                        help="comma-separated grad-accum factors for the "
                        "--step search space")
    parser.add_argument("--verify_steps", type=int, default=5,
                        help="optimizer steps per --step candidate for the "
                        "loss-trajectory oracle check")
    parser.add_argument("--virtual_devices", type=int, default=0,
                        help="CPU only: split the host into N virtual "
                        "devices before tuning (exercises dp>1 schedules "
                        "like the overlapped ZeRO-1 step)")
    parser.add_argument("--platform", default=None, choices=("cpu", "tpu"))
    parser.add_argument("--selftest", action="store_true",
                        help="tiny-shape end-to-end check: tune, round-trip "
                        "the DB, verify numerics, and prove a warmed engine "
                        "compiles nothing on its first request")
    return parser


def selftest() -> int:
    """The `make tune-smoke` acceptance loop (ISSUE 4); CPU-safe, <1 min."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.compiler import autotune
    from deeplearning_mpi_tpu.compiler import cache as ccache
    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.ops.pallas import flash_attention
    from deeplearning_mpi_tpu.serving import (
        EngineConfig,
        RequestState,
        ServingEngine,
    )
    from deeplearning_mpi_tpu.telemetry import MetricsRegistry

    ok = True

    def check(cond: bool, label: str) -> None:
        nonlocal ok
        print(("PASS " if cond else "FAIL ") + label, file=sys.stderr)
        ok = ok and cond

    with tempfile.TemporaryDirectory(prefix="dmt_tune_") as td:
        # 1. Tune both kernels on tiny shapes; persist the DB.
        db_path = Path(td) / "tuning.json"
        db = autotune.TuningDB(db_path)
        attn_shape = (1, 64, 2, 16)
        attn = autotune.tune_flash_attention(
            attn_shape, db=db, candidates=(16, 32, 64), repeats=1,
        )
        check(bool(attn), f"attention tuned: {attn}")
        dec = autotune.tune_flash_decode(
            (2, 64, 2, 16), db=db, blocks=(16, 32), repeats=1,
        )
        check(dec.get("schedule") in ("kernel", "einsum"),
              f"decode tuned: {dec}")
        db.save()

        # 2. Round-trip: reload and look the winners back up.
        db2 = autotune.TuningDB.load(db_path)
        check(len(db2) == 2, f"DB round-trip: {len(db2)} entries")
        check(
            db2.lookup("flash_attention", attn_shape, jnp.float32) == attn,
            "DB lookup returns the recorded winner",
        )

        # 3. Tuned kernel matches the default kernel numerically — both
        # explicitly-threaded blocks and the DB-consulting default path.
        kq, kk, kv = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(kq, attn_shape)
        k = jax.random.normal(kk, attn_shape)
        v = jax.random.normal(kv, attn_shape)
        default_out = flash_attention(q, k, v)
        tuned_out = flash_attention(
            q, k, v, block_q=attn["block_q"], block_k=attn["block_k"]
        )
        check(
            bool(jnp.allclose(tuned_out, default_out, rtol=2e-5, atol=2e-5)),
            "tuned blocks match default-kernel output",
        )
        autotune.set_default_db(db2)
        try:
            db_out = flash_attention(q, k, v)  # blocks resolved from the DB
            check(
                bool(jnp.allclose(db_out, default_out, rtol=2e-5, atol=2e-5)),
                "DB-resolved blocks match default-kernel output",
            )
        finally:
            autotune.set_default_db(None)

        # 4. Warm-engine contract under the persistent compile cache main()
        # placed ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache —
        # never a temp dir: the path is part of the cache key): a second
        # engine's warmup deserializes (cache hits) and its first request
        # triggers zero compiles (the trace counter stays put).
        cfg = TransformerConfig.tiny()
        model = TransformerLM(config=cfg, dtype=jnp.float32)
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        eng_cfg = EngineConfig(
            max_slots=2, block_size=8, num_blocks=16,
            max_blocks_per_seq=4, prefill_chunk=8, max_queue=8,
        )

        def make_engine():
            registry = MetricsRegistry()
            engine = ServingEngine(
                cfg, params, eng_cfg,
                dtype=jnp.float32, registry=registry,
            )
            engine.warmup(cache=ccache.CompileCache(registry=registry))
            return engine, registry

        make_engine()  # populates the persistent cache if it was cold
        engine, registry = make_engine()  # warm: must hit
        hits = registry.counter("compile_cache_hit_total").value
        check(hits > 0, f"warm engine start: compile_cache_hit_total={hits}")

        before = registry.counter("serve_compile_total").value
        req = engine.submit(np.arange(1, 9, dtype=np.int32), 4)
        while not engine.scheduler.idle():
            engine.step()
        after = registry.counter("serve_compile_total").value
        check(
            req.state is RequestState.FINISHED,
            f"first request finished ({len(req.generated)} tokens)",
        )
        check(
            after == before,
            f"zero compiles on first request "
            f"(serve_compile_total {before} -> {after})",
        )

        # 5. Whole-step schedule tuning: two candidates, oracle-first loss
        # verification, persisted winner, never-raise consult semantics.
        step_params = autotune.tune_step_schedule(
            "lm", batch_size=4, seq_len=16, db=db,
            candidates=[
                {"remat": "none", "grad_accum": 1,
                 "donate": True, "overlap": False},
                {"remat": "dots", "grad_accum": 2,
                 "donate": True, "overlap": False},
            ],
            steps=3, repeats=1,
        )
        check(
            step_params.get("remat") in ("none", "dots"),
            f"step schedule tuned: {step_params}",
        )
        db.save()
        from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh

        step_mesh = create_mesh(MeshSpec(data=len(jax.devices())))
        back = autotune.tuned_step_schedule(
            "lm", (4, 16), step_mesh, db=autotune.TuningDB.load(db_path)
        )
        check(back == step_params, f"step entry round-trips: {back}")
        corrupt = Path(td) / "corrupt.json"
        corrupt.write_text("{not json")
        check(
            autotune.tuned_step_schedule(
                "lm", (4, 16), step_mesh,
                db=autotune.TuningDB.load(corrupt),
            ) is None,
            "corrupt DB consult degrades to None, never raises",
        )

        # 6. Speculative depth search: real engines race per candidate k
        # (greedy parity makes it a pure throughput race), the winner and
        # its measured acceptance rate persist and round-trip.
        spec = autotune.tune_spec_k(
            draft_layers=1, db=db, candidates=(0, 2),
            num_requests=2, max_new_tokens=8,
        )
        check(
            isinstance(spec.get("spec_k"), int) and spec["spec_k"] in (0, 2),
            f"spec_k tuned: {spec}",
        )
        db.save()
        autotune.set_default_db(autotune.TuningDB.load(db_path))
        try:
            from deeplearning_mpi_tpu.models import (
                TransformerConfig as _TC,
            )
            back = autotune.tuned_spec_k(_TC.tiny(), 1, jnp.float32)
            check(back == spec, f"spec_k entry round-trips: {back}")
        finally:
            autotune.set_default_db(None)

    print("tune-smoke " + ("OK" if ok else "FAILED"), file=sys.stderr)
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform(args.platform)
    if args.virtual_devices:
        # Must precede first backend use — bootstrap refuses otherwise.
        from deeplearning_mpi_tpu.runtime import bootstrap

        bootstrap.set_virtual_cpu_devices(args.virtual_devices)
    if args.selftest:
        return selftest()
    if not (args.attn_shape or args.decode_shape or args.step
            or args.spec_k is not None):
        print("nothing to tune: pass --attn_shape, --decode_shape, "
              "--spec_k, and/or --step (or --selftest)",
              file=sys.stderr)
        return 1

    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.compiler import autotune

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    blocks = (
        tuple(int(b) for b in args.blocks.split(",")) if args.blocks else None
    )
    db = autotune.TuningDB.load(args.db)
    print(f"backend: {jax.default_backend()}, DB: {args.db} "
          f"({len(db)} existing entries)", file=sys.stderr)
    for spec in args.attn_shape:
        shape = _parse_shape(spec, "--attn_shape")
        params = autotune.tune_flash_attention(
            shape, dtype, db=db, candidates=blocks, repeats=args.repeats,
        )
        print(f"flash_attention {spec}: {params or 'no legal candidate'}",
              file=sys.stderr)
    for spec in args.decode_shape:
        shape = _parse_shape(spec, "--decode_shape")
        params = autotune.tune_flash_decode(
            shape, dtype, heads=args.heads, db=db, blocks=blocks,
            repeats=args.repeats,
        )
        print(f"flash_decode {spec}: {params}", file=sys.stderr)
    if args.spec_k is not None:
        params = autotune.tune_spec_k(
            draft_layers=args.spec_k, dtype=dtype, db=db,
        )
        print(f"spec_k (draft_layers={args.spec_k}): {params}",
              file=sys.stderr)
    for spec in args.step:
        batch, seq = _parse_shape(spec, "--step", ndims=2, example="8x2048")
        grad_accums = tuple(int(g) for g in args.grad_accums.split(","))
        dp = len(jax.devices())
        params = autotune.tune_step_schedule(
            args.step_model, batch_size=batch, seq_len=seq, dtype=dtype,
            db=db, candidates=autotune.step_candidates(
                dp, grad_accums=grad_accums
            ),
            steps=args.verify_steps, repeats=args.repeats,
        )
        print(f"step {args.step_model} {spec}: "
              f"{params or 'no viable candidate'}", file=sys.stderr)
    db.save()
    print(f"wrote {args.db}: {len(db)} entries", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
