#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python3 chip_smoke.py`` drives the main path once, through the entry points
a user would call, at the full width of the dense 110M LM (12 layers, d_model
768, 12 heads x 64, d_ff 2048, seq 2048, bf16, Pallas flash attention):

1. **kernels** — every Pallas kernel the main path and ``bench.py`` reach,
   compiled by Mosaic (``interpret=False``) and compared with its
   ``jax.numpy`` reference on the device;
2. **trainer** — ``cli.train_lm.main``: >= 8 optimizer steps, one eval, one
   checkpoint; read back from ``metrics.jsonl``, not trusted from the exit
   code;
3. **server, parity** — ``cli.serve_lm.main --selftest --warmup`` with a pool
   that reaches 2k context;
4. **server, hand-off** — ``cli.serve_lm.main --model_dir`` on the checkpoint
   phase 2 wrote.

Everything runs in THIS process: a chip belongs to one process at a time, so
nothing here spawns a child that needs it. There is no CPU mode — without a
TPU the script exits non-zero before any phase and prints no result. Any
phase failing raises, so the exit code is non-zero; nothing is caught and
carried on from. The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The phases are functions of a :class:`Size`, so ``tests/test_chip_smoke.py``
drives them at toy size on CPU with the platform check stubbed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Size:
    """Every dimension the phases use. The defaults are the full width of
    ``BENCH_r04``'s ``transformer_lm_2k_flash`` model; only a test shrinks
    them."""

    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    head_dim: int = 64
    d_ff: int = 2048
    #: compute dtype everywhere. XLA:CPU cannot run the interpreted kernels'
    #: bf16 dots, so the CPU test passes float32.
    dtype: str = "bfloat16"
    seq_len: int = 2048
    #: per chip; the trainer's global batch is this times the device count.
    batch: int = 8
    learning_rate: float = 3e-4
    # serving pool: max_blocks_per_seq * block_size = 2048 positions
    max_slots: int = 8
    block_size: int = 16
    max_blocks_per_seq: int = 128
    num_blocks: int = 1100
    prefill_chunk: int = 128
    num_requests: int = 16
    prompt_len_min: int = 64
    prompt_len_max: int = 512
    max_new_tokens: int = 64
    handoff_requests: int = 4
    # kernels
    window: int = 512
    wide_head_dim: int = 128
    #: flash-decode is dispatched only above ops.attention.DECODE_DENSE_MAX.
    decode_len: int = 8192
    decode_batches: tuple[int, ...] = (1, 8, 32)
    decode_window: int = 1024

    def model_flags(self) -> list[str]:
        return [
            "--num_layers", str(self.num_layers),
            "--num_heads", str(self.num_heads),
            "--head_dim", str(self.head_dim),
            "--d_model", str(self.d_model),
            "--d_ff", str(self.d_ff),
            "--dtype", self.dtype,
        ]

    def engine_flags(self) -> list[str]:
        return [
            "--max_slots", str(self.max_slots),
            "--block_size", str(self.block_size),
            "--max_blocks_per_seq", str(self.max_blocks_per_seq),
            "--num_blocks", str(self.num_blocks),
            "--prefill_chunk", str(self.prefill_chunk),
            "--prompt_len_min", str(self.prompt_len_min),
            "--prompt_len_max", str(self.prompt_len_max),
            "--max_new_tokens", str(self.max_new_tokens),
            "--rate", "100",
        ]


def check(ok: bool, message: str) -> None:
    """A failed check fails the phase (and so the run). Not ``assert``:
    ``python -O`` must not turn the smoke into a no-op."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {message}")


def require_tpu() -> dict:
    """First act: the device as jax reports it, or a one-line failure."""
    import importlib.metadata

    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but jax.devices()[0] is platform "
            f"{dev.platform!r} ({dev.device_kind}); there is no CPU mode",
            file=sys.stderr,
        )
        raise SystemExit(2)
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(
        f"chip_smoke: {device['count']} x {device['kind']} | jax "
        f"{jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}",
        file=sys.stderr,
    )
    return device


def _records(path: Path) -> list[dict]:
    return [json.loads(ln) for ln in path.read_text().splitlines() if ln.strip()]


# -- phase 1: kernels ---------------------------------------------------------

def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-6))


def _run_kernel_case(name, fn, ref_fn, args, *, interpret, min_mosaic, tol):
    """Compile ``fn`` (timing it), prove Mosaic compiled it, run it and the
    reference on the device, compare every output leaf."""
    import jax

    from deeplearning_mpi_tpu.compiler.aot import mosaic_call_count

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    calls = mosaic_call_count(compiled)
    if not interpret:
        check(
            calls >= min_mosaic,
            f"{name}: compiled HLO holds {calls} Mosaic custom call(s), "
            f"expected >= {min_mosaic} — a reference path ran instead",
        )
    got = jax.tree.leaves(compiled(*args))
    ref = jax.tree.leaves(jax.jit(ref_fn)(*args))
    err = max(_rel_err(g, r) for g, r in zip(got, ref, strict=True))
    check(
        math.isfinite(err) and err <= tol,
        f"{name}: max relative error {err:.3g} vs reference exceeds {tol}",
    )
    return {"compile_s": round(compile_s, 2), "mosaic_calls": calls,
            "rel_err": float(f"{err:.3g}")}


def _with_grads(attn):
    """o, dq, dk, dv of ``sum(attn(q, k, v) * do)`` — the forward and both
    backward kernels in one program."""
    import jax

    def fn(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return (o, *vjp(do))
    return fn


def _kernel_cases(size: Size, interpret: bool):
    """Yield ``(name, fn, ref_fn, args, min_mosaic, tol)`` for every kernel
    variant. Shapes follow the main path (B8 H12 S2048 D64) and bench.py."""
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.ops.attention import (
        batched_decode_attention,
        dense_attention,
    )
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_attention_bhsd,
        flash_bwd_block,
        flash_fwd_block,
    )
    from deeplearning_mpi_tpu.ops.pallas.flash_decode import (
        flash_decode,
        quantize_kv,
    )

    dtype = jnp.dtype(size.dtype)
    B, H, S, D = size.batch, size.num_heads, size.seq_len, size.head_dim
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731 — BSHD <-> BHSD

    def qkv(shape, seed):
        ks = jax.random.split(jax.random.key(seed), 4)
        return tuple(jax.random.normal(k, shape, dtype) for k in ks)

    # flash fwd + dq + dkv, BHSD-native (the trainer's entry), full causal,
    # then windowed, then at the wide head dim.
    for name, shape, window in (
        ("flash_bhsd", (B, H, S, D), None),
        ("flash_bhsd_windowed", (B, H, S, D), size.window),
        ("flash_bhsd_d128", (max(B // 2, 1), H, S, size.wide_head_dim), None),
    ):
        yield (
            name,
            _with_grads(lambda q, k, v, w=window: flash_attention_bhsd(
                q, k, v, window=w, interpret=interpret)),
            _with_grads(lambda q, k, v, w=window: swap(dense_attention(
                swap(q), swap(k), swap(v), window=w))),
            qkv(shape, 0), 3, 3e-2,
        )
    # BSHD entry (generate.py's TPU-only prefill branch, Ulysses inner).
    yield (
        "flash_bshd",
        _with_grads(lambda q, k, v: flash_attention(
            q, k, v, interpret=interpret)),
        _with_grads(dense_attention), qkv((B, S, H, D), 1), 3, 3e-2,
    )
    # The ring schedule's per-rotation inner: non-causal visiting block,
    # lse out, f32 partials and f32 grads (parallel/ring_flash.py). One
    # device is enough to prove Mosaic takes these variants.
    blk = min(1024, S)

    def ring_inner(q, k, v, do):
        o, lse = flash_fwd_block(
            q, k, v, False, blk, blk, interpret, with_lse=True,
            out_dtype=jnp.float32,
        )
        grads = flash_bwd_block(
            q, k, v, o.astype(q.dtype), do, lse, False, blk, blk, interpret,
            grad_dtype=jnp.float32,
        )
        return (o, *grads)

    yield (
        "ring_flash_inner", ring_inner,
        _with_grads(lambda q, k, v: dense_attention(q, k, v, causal=False)),
        qkv((max(B // 4, 1), S, H, D), 2), 3, 3e-2,
    )

    # flash-decode: per-row fill levels over a long buffer.
    L = size.decode_len

    def decode_args(batch, seed):
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (batch, 1, H, D), dtype)
        k = jax.random.normal(ks[1], (batch, L, H, D), dtype)
        v = jax.random.normal(ks[2], (batch, L, H, D), dtype)
        # Rows at different fills, the last one full.
        idx = jnp.linspace(L // 3, L - 1, batch).astype(jnp.int32)
        return q, k, v, idx

    def decode_ref(window=None):
        return lambda q, k, v, idx: batched_decode_attention(
            q, k, v, idx, window=window)

    for batch in size.decode_batches:
        yield (
            f"flash_decode_b{batch}",
            lambda q, k, v, idx: flash_decode(
                q, k, v, idx, interpret=interpret),
            decode_ref(), decode_args(batch, 3), 1, 3e-2,
        )
    mid = size.decode_batches[len(size.decode_batches) // 2]
    yield (
        "flash_decode_windowed",
        lambda q, k, v, idx: flash_decode(
            q, k, v, idx, window=size.decode_window, interpret=interpret),
        decode_ref(size.decode_window), decode_args(mid, 4), 1, 3e-2,
    )

    def decode_int8(q, k, v, idx):
        (qk, sk), (qv, sv) = quantize_kv(k), quantize_kv(v)
        return flash_decode(
            q, qk, qv, idx, k_scale=sk, v_scale=sv, interpret=interpret)

    def decode_int8_ref(q, k, v, idx):
        (qk, sk), (qv, sv) = quantize_kv(k), quantize_kv(v)
        deq = lambda x, s: (x.astype(jnp.float32) * s[..., None]).astype(dtype)  # noqa: E731
        return decode_ref()(q, deq(qk, sk), deq(qv, sv), idx)

    yield ("flash_decode_int8", decode_int8, decode_int8_ref,
           decode_args(mid, 5), 1, 3e-2)


def _ring_flash_case(size: Size):
    """Ring-flash over every local device (needs >= 2): the full schedule —
    kernels, ppermute rotations, lse merge, its own VJP — against dense."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning_mpi_tpu.ops.attention import dense_attention
    from deeplearning_mpi_tpu.parallel import make_ring_attention_fn
    from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh

    n = jax.device_count()
    mesh = create_mesh(MeshSpec(data=1, seq=n))
    ring = make_ring_attention_fn(mesh, flash=True)
    shape = (2, size.seq_len * 2, size.num_heads, size.head_dim)
    ks = jax.random.split(jax.random.key(7), 4)
    sharding = NamedSharding(mesh, P(None, "seq", None, None))
    args = tuple(
        jax.device_put(
            jax.random.normal(k, shape, jnp.dtype(size.dtype)), sharding
        )
        for k in ks
    )

    return (
        f"ring_flash_sp{n}", _with_grads(ring), _with_grads(dense_attention),
        args, 3, 3e-2,
    )


def phase_kernels(size: Size, *, interpret: bool = False) -> dict:
    """Every kernel variant runs even after one fails, so one chip call
    yields every verdict; the phase then fails with all the reasons."""
    import jax

    cases = list(_kernel_cases(size, interpret))
    if jax.device_count() >= 2:
        cases.append(_ring_flash_case(size))
    verdicts: dict[str, dict] = {}
    failures: list[str] = []
    for name, fn, ref_fn, args, min_mosaic, tol in cases:
        try:
            verdicts[name] = _run_kernel_case(
                name, fn, ref_fn, args,
                interpret=interpret, min_mosaic=min_mosaic, tol=tol,
            )
        except Exception as err:  # noqa: BLE001 — Mosaic raises its own types; collected, re-raised below
            reason = " ".join(str(err).split())[:600]
            verdicts[name] = {"failed": reason}
            failures.append(f"{name}: {reason}")
        print(f"chip_smoke: kernel {name}: {verdicts[name]}", file=sys.stderr)
    check(not failures, "kernel phase failed — " + " || ".join(failures))
    return verdicts


# -- phase 2: trainer ---------------------------------------------------------

def phase_train(size: Size, workdir: Path, *, platform: str,
                mesh_flags: tuple[str, ...] = ()) -> dict:
    """``cli.train_lm.main`` for one epoch of >= 8 steps with eval and a
    checkpoint, over every device present (``mesh_flags`` picks the layout;
    the default is pure data parallel); verdict read back from what it
    wrote."""
    import jax

    from deeplearning_mpi_tpu.cli import train_lm
    from deeplearning_mpi_tpu.resilience.integrity import (
        dir_digests,
        read_manifest,
    )

    workdir = workdir / ("train" + "".join(mesh_flags).replace("--", "_"))
    workdir.mkdir()
    metrics_dir = workdir / "metrics"
    model_dir = workdir / "models"
    n_devices = jax.device_count()
    global_batch = size.batch * n_devices
    # Real bytes, not the synthetic motifs: their unigram statistics give a
    # loss that falls by whole nats within a few steps, where the motifs
    # (uniform tokens, learnable only by in-context copying) move it by
    # noise. The corpus is the package's own tracked sources.
    # Ten global batches: train_lm holds the last tenth out for the one eval
    # batch, leaving nine optimizer steps.
    need = 10 * global_batch * size.seq_len
    package = Path(__file__).resolve().parent / "deeplearning_mpi_tpu"
    corpus = b"".join(
        p.read_bytes() for p in sorted(package.rglob("*.py"))
    )[:need]
    check(len(corpus) == need, f"package sources hold only {len(corpus)} bytes")
    (workdir / "corpus.txt").write_bytes(corpus)
    rc = train_lm.main([
        "--platform", platform, *size.model_flags(),
        "--seq_len", str(size.seq_len), "--batch_size", str(global_batch),
        "--text_file", str(workdir / "corpus.txt"),
        "--learning_rate", str(size.learning_rate),
        "--attention", "flash", "--num_epochs", "1", "--eval_every", "1",
        "--aot_warmup", "--metrics_dir", str(metrics_dir),
        "--model_dir", str(model_dir), "--log_dir", str(workdir / "logs"),
        *mesh_flags,
    ])
    check(rc == 0, f"train_lm.main returned {rc}")

    records = _records(metrics_dir / "metrics.jsonl")
    steps = [r for r in records if r["kind"] == "step"]
    check(len(steps) >= 8, f"only {len(steps)} optimizer steps recorded")
    skipped = [r["step"] for r in steps if r["finite"] != 1]
    check(not skipped, f"non-finite (skipped) steps: {skipped}")
    losses = [r["loss"] for r in steps]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]:.4f}, last {losses[-1]:.4f}")
    epoch = next(r for r in records if r["kind"] == "epoch")
    check(math.isfinite(epoch.get("eval_loss", math.nan)),
          f"no finite eval_loss in the epoch record: {epoch}")
    summary = next(r for r in records if r["kind"] == "run_summary")
    mosaic = int(summary.get("train_step_mosaic_calls", 0))
    if platform == "tpu":
        # fwd, dq and dkv per layer. flash_attention returns the dense op
        # without a word when its blocks do not tile the sequence.
        check(
            mosaic >= 3 * size.num_layers,
            f"compiled train step holds {mosaic} Mosaic custom calls, "
            f"expected >= {3 * size.num_layers}: flash attention did not run",
        )
    for what in ("state", "batch"):
        on = int(summary.get(f"train_{what}_devices", 0))
        check(on == n_devices,
              f"the {what} occupies {on} of {n_devices} devices")

    ckpt = model_dir / "lm"
    manifest = read_manifest(ckpt, 0)
    check(manifest is not None, f"no integrity manifest for epoch 0 in {ckpt}")
    check(dir_digests(ckpt / "0") == manifest,
          f"checkpoint {ckpt / '0'} does not match its manifest")
    return {
        "devices": n_devices,
        "mesh_flags": " ".join(mesh_flags),
        "steps": len(steps),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "eval_loss": round(epoch["eval_loss"], 4),
        "step_ms_p50": epoch.get("step_ms_p50"),
        "compile_s": summary.get("train_compile_seconds"),
        "cache_hits": summary.get("compile_cache_hit_total"),
        "cache_misses": summary.get("compile_cache_miss_total"),
        "mosaic_calls": mosaic,
        "model_dir": str(model_dir),
    }


# -- phases 3 and 4: server ---------------------------------------------------

def _serve(argv: list[str], metrics_file: Path) -> tuple[dict, dict]:
    """Run ``serve_lm.main``; return its (serve_warmup, serve_summary)
    records. Completions print on stdout, so they are redirected to stderr:
    the last stdout line belongs to the result."""
    import contextlib

    from deeplearning_mpi_tpu.cli import serve_lm

    with contextlib.redirect_stdout(sys.stderr):
        rc = serve_lm.main([*argv, "--warmup", "--metrics_file", str(metrics_file)])
    check(rc == 0, f"serve_lm.main {' '.join(argv[:1])} returned {rc}")
    records = _records(metrics_file)
    warm = next(r for r in records if r["kind"] == "serve_warmup")
    summary = next(r for r in records if r["kind"] == "serve_summary")
    # Zero first-request compiles: every program was compiled by warmup.
    check(
        summary["serve_compile_total"] == warm["serve_compile_total"],
        f"serve_compile_total moved after warm-up: "
        f"{warm['serve_compile_total']} -> {summary['serve_compile_total']}",
    )
    return warm, summary


def _serve_verdict(n: int, size: Size, warm: dict, summary: dict) -> dict:
    done = summary.get("serve_requests_completed", 0)
    check(done == n, f"{done:.0f} of {n} requests FINISHED")
    tokens = summary.get("serve_tokens_generated", 0)
    check(tokens == n * size.max_new_tokens,
          f"{tokens:.0f} tokens generated, expected {n * size.max_new_tokens}")
    return {
        "requests": n,
        "tokens": int(tokens),
        "programs_compiled": int(warm["serve_compile_total"]),
        "cache_hits": warm.get("compile_cache_hit_total"),
        "cache_misses": warm.get("compile_cache_miss_total"),
        "ttft_s_p50": summary.get("serve_ttft_s_p50"),
        "tpot_s_p50": summary.get("serve_tpot_s_p50"),
    }


def phase_serve_selftest(size: Size, workdir: Path, *, platform: str) -> dict:
    """The paged engine against ``models/generate.py``: ``--selftest``
    returns 0 only when every completion matches offline greedy decode
    token for token."""
    warm, summary = _serve(
        ["--selftest", "--platform", platform, *size.model_flags(),
         *size.engine_flags(), "--num_requests", str(size.num_requests)],
        workdir / "serve_selftest.jsonl",
    )
    return _serve_verdict(size.num_requests, size, warm, summary)


def phase_serve_handoff(size: Size, workdir: Path, model_dir: str, *,
                        platform: str) -> dict:
    """Serve the checkpoint the trainer phase wrote (params-only restore on
    the device)."""
    warm, summary = _serve(
        ["--model_dir", model_dir, "--platform", platform,
         *size.model_flags(), *size.engine_flags(),
         "--num_requests", str(size.handoff_requests)],
        workdir / "serve_handoff.jsonl",
    )
    return _serve_verdict(size.handoff_requests, size, warm, summary)


def phase_hello_world(*, platform: str) -> dict:
    """``cli.hello_world.main``: broadcast, ring and psum across every
    device — the collectives before anything is trained on them."""
    import contextlib

    import jax

    from deeplearning_mpi_tpu.cli import hello_world

    with contextlib.redirect_stdout(sys.stderr):
        rc = hello_world.main(["--platform", platform])
    check(rc == 0, f"hello_world.main returned {rc}")
    return {"devices": jax.device_count()}


def run_phases(size: Size, workdir: Path, *, platform: str,
               interpret: bool = False) -> dict:
    """Every phase in order; the first failure propagates. The trainer
    always spans the devices present (8 sequences a chip); on a host of four
    or more it runs a second time on a data x model mesh with ZeRO-1, after
    the collectives smoke. The server is one device per replica by design."""
    import jax

    results: dict[str, dict] = {}
    n = jax.device_count()
    hybrid = ("--dp", str(n // 2), "--tp", "2", "--zero")
    multi = n >= 4 and n % 2 == 0
    phases = [
        ("kernels", lambda: phase_kernels(size, interpret=interpret)),
        *([("hello_world", lambda: phase_hello_world(platform=platform))]
          if multi else []),
        ("train", lambda: phase_train(size, workdir, platform=platform)),
        *([("train_dp_tp_zero", lambda: phase_train(
            size, workdir, platform=platform, mesh_flags=hybrid))]
          if multi else []),
        ("serve_selftest",
         lambda: phase_serve_selftest(size, workdir, platform=platform)),
        ("serve_handoff",
         lambda: phase_serve_handoff(
             size, workdir, results["train"]["model_dir"], platform=platform)),
    ]
    for name, phase in phases:
        t0 = time.perf_counter()
        results[name] = phase()
        results[name]["wall_s"] = round(time.perf_counter() - t0, 1)
        print(f"chip_smoke: phase {name} ok: {json.dumps(results[name])}",
              file=sys.stderr)
    return results


def main() -> int:
    t0 = time.perf_counter()
    # Before the first backend use: the compile cache goes where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache.
    from deeplearning_mpi_tpu.compiler import cache

    cache_dir = cache.configure()
    device = require_tpu()
    # The 1.3 GB checkpoint and the logs are scratch, gone at exit.
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        results = run_phases(Size(), Path(tmp), platform="tpu")
    print(json.dumps({
        "phases": results,
        "compile_cache_dir": str(cache_dir),
        "wall_s": round(time.perf_counter() - t0, 1),
    }))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
