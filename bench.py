"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

Prints ONE JSON line:
    {"metric": "resnet50_bf16_images_per_sec_per_chip", "value": ..., "unit":
     "images/s/chip", "vs_baseline": ...}

Workload: the BASELINE.md primary config — ResNet-50, bf16 compute / f32
params, full jitted train step (forward + backward + SGD-momentum update +
BN stat update), synthetic on-device data so the measurement isolates the
training step (input pipeline throughput is benchmarked separately by the
trainers' images/s logging). The reference publishes no numbers (BASELINE.md:
"published: {}"), so ``vs_baseline`` is measured against the documented
stand-in target below.

Baseline constant: 1500 images/s — a single A100's typical ResNet-50
ImageNet-class throughput under PyTorch DDP with mixed precision (the
BASELINE.md north star is "≥ single-A100 step throughput per chip"). We run
the CIFAR-sized 32×32 input the reference's trainer actually uses
(``pytorch/resnet/main.py:91-92``) at batch 1024; to keep the comparison
honest against the 224×224 A100 figure we ALSO report the 224×224 result in
the details and use IT for vs_baseline when it runs.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

# Single-A100 ResNet-50 mixed-precision throughput stand-in. Public anchor:
# NVIDIA's DeepLearningExamples ResNet-50 v1.5 reports ~2,200 img/s for one
# A100-80GB at AMP (training perf table); typical user-reported PyTorch DDP
# figures without DALI/fused-ops land at 1,200-1,800. 1,500 is the midpoint
# used as the "≥ single-A100 per chip" BASELINE.md north star.
A100_RESNET50_224_IMG_PER_S = 1500.0

# Round-4 single-stream decode harness result (tools/bench_decode.py
# bench_e2e: ~110M LM, one request at a time, blended prefill+decode
# positions/s). BENCH_r04.json's details record it was measured on **TPU
# v5 lite**, while every later round ran on CPU — so a raw ratio against
# this constant compares chips, not code. The speculative+batched engine's
# >=5x target is therefore judged on the SAME harness: bench_spec_decode
# re-runs the r04 single-stream recipe fresh in the same process
# (speedup_vs_single_stream) and reports vs_r04 against this constant only
# as the cross-round anchor. See docs/PERF_ANALYSIS.md §12.
R04_SINGLE_STREAM_POSITIONS_PER_S = 1341.0

# Analytic forward FLOPs per image for ResNet-50 (2*MACs over convs+fc), by
# input size; training step ≈ 3x forward. This is the community MFU
# convention — XLA's HLO flop counter reports ~2x this for the same step
# because it prices backward strided/dilated convs at their zero-inserted
# shapes, so the HLO-derived figure is kept in details as mfu_hlo_counted.
RESNET50_FWD_FLOPS = {224: 4.089e9, 32: 84.0e6}


def _timed_steps(step, state, batch, steps: int) -> dict:
    """Shared warmup + timing scaffold for every sub-bench.

    Warmup (compile + 2 hot steps), then ``steps`` timed executions, synced
    by fetching the scalar loss the whole step chain must complete to
    produce. Returns step time and ``n_chips`` — the devices the batch and
    state actually OCCUPY, not ``jax.device_count()``: these benches build
    no mesh, so on a four-chip host the work still sits on one device and
    dividing by four would report a quarter of the true per-chip rate.
    Callers derive their own domain-specific rates (images/s, tokens/s, MFU).
    """
    from deeplearning_mpi_tpu.runtime.mesh import occupied_devices
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    n_chips = occupied_devices((state, batch))

    for _ in range(3):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])  # the whole step chain must complete to produce this
    dt = time.perf_counter() - t0
    return {
        "steps": steps,
        "step_time_ms": dt / steps * 1e3,
        "steps_per_s": steps / dt,
        "n_chips": n_chips,
    }


def bench_train_step(image_size: int, batch_size: int, steps: int = 20) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models import resnet50
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer

    model = resnet50(num_classes=10, dtype=jnp.bfloat16)
    tx = build_optimizer("sgd", 0.1, momentum=0.9, weight_decay=1e-5)
    state = create_train_state(
        model, jax.random.key(0), jnp.zeros((1, image_size, image_size, 3)), tx
    )
    step = make_train_step("classification")

    rng = jax.random.key(1)
    images = jax.random.normal(rng, (batch_size, image_size, image_size, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch_size,), 0, 10)
    batch = {"image": images, "label": labels}

    # One AOT compile serves both the HLO flop count (mfu_hlo_counted) and
    # the timed loop — calling the compiled object directly avoids a second
    # trace/compile through the jit dispatch cache.
    from deeplearning_mpi_tpu.telemetry.flops import (
        device_peak_flops,
        xla_cost_analysis,
    )

    step = step.lower(state, batch).compile()
    flops_per_step = xla_cost_analysis(step).get("flops")
    peak_tflops = device_peak_flops() / 1e12

    timing = _timed_steps(step, state, batch, steps)
    result = {
        "image_size": image_size,
        "batch_size": batch_size,
        **timing,
        "images_per_s_per_chip": batch_size * timing["steps_per_s"]
        / timing["n_chips"],
    }
    fwd_flops = RESNET50_FWD_FLOPS.get(image_size)
    if fwd_flops:
        analytic_tflops = (
            3 * fwd_flops * result["images_per_s_per_chip"] / 1e12
        )
        result["achieved_tflops_per_chip"] = round(analytic_tflops, 1)
        result["mfu"] = round(analytic_tflops / peak_tflops, 3)
    if flops_per_step:
        hlo_tflops = (
            flops_per_step * timing["steps_per_s"] / 1e12 / timing["n_chips"]
        )
        result["mfu_hlo_counted"] = round(hlo_tflops / peak_tflops, 3)
    return result


def bench_unet(image_size: int = 512, batch_size: int = 8, steps: int = 10) -> dict:
    """UNet-2D training throughput — the second BASELINE.md headline metric
    ("images/sec/chip (ResNet-50, UNet-2D)"). Full reference topology
    (64..1024 channels, transpose-conv up path), bf16 compute, Adam +
    grad-clip 1.0 (the reference trainer's optimizer, unet/train.py:160,194)."""
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models import UNet
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer

    model = UNet(dtype=jnp.bfloat16)
    tx = build_optimizer("adam", 1e-4, clip_norm=1.0)
    state = create_train_state(
        model, jax.random.key(0), jnp.zeros((1, image_size, image_size, 3)), tx
    )
    step = make_train_step("segmentation")
    rng = jax.random.key(1)
    batch = {
        "image": jax.random.normal(
            rng, (batch_size, image_size, image_size, 3), jnp.float32
        ),
        "mask": (
            jax.random.uniform(rng, (batch_size, image_size, image_size)) > 0.5
        ).astype(jnp.float32),
    }
    timing = _timed_steps(step, state, batch, steps)
    return {
        "image_size": image_size,
        "batch_size": batch_size,
        **timing,
        "images_per_s_per_chip": round(
            batch_size * timing["steps_per_s"] / timing["n_chips"], 1
        ),
    }


def bench_lm(seq_len: int = 2048, batch_size: int = 8, steps: int = 10,
             remat: bool = False, loss_chunk: int = 0) -> dict:
    """TransformerLM train-step throughput with the compiled Pallas flash
    kernel: tokens/s/chip + MFU. Default config = the 110M-param
    TransformerConfig (768d x 12L) at 2k sequence, bf16. ``remat=True`` is
    the long-context memory recipe; ``loss_chunk`` adds the chunked
    head+loss (wall 3) needed at 64k."""
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import flash_attention_bhsd
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer

    config = TransformerConfig()
    model = TransformerLM(
        config=config, dtype=jnp.bfloat16, attention_fn=flash_attention_bhsd,
        remat=remat,
        # chunked head+loss consumes (prehead_x, head_kernel), not logits
        return_prehead=loss_chunk > 0,
    )
    tx = build_optimizer("adam", 3e-4, clip_norm=1.0)
    state = create_train_state(
        model, jax.random.key(0), jnp.zeros((1, seq_len), jnp.int32), tx
    )
    step = make_train_step("lm", loss_chunk=loss_chunk)
    tokens = jax.random.randint(
        jax.random.key(1), (batch_size, seq_len), 0, config.vocab_size
    )
    batch = {"tokens": tokens}

    # Provenance-only consult of the step-schedule tuning space: the bench
    # measures the config it was ASKED to run (changing the workload under a
    # DB hit would make BENCH_*.json numbers incomparable across runs), but
    # the looked-up `step|...` entry — and the fact of the lookup, via the
    # DB's consulted log — rides the result so a reader can tell whether a
    # tuned schedule existed for this exact shape/mesh/dtype.
    from deeplearning_mpi_tpu.compiler import aot, autotune
    from deeplearning_mpi_tpu.telemetry.flops import device_peak_flops

    # flash_attention returns the dense op without a word when its blocks do
    # not tile the sequence; a chip number under this cell's name must come
    # from the kernels (fwd, dq, dkv per layer).
    step = step.lower(state, batch).compile()
    mosaic_calls = aot.mosaic_call_count(step)
    if jax.default_backend() == "tpu" and mosaic_calls < 3 * config.num_layers:
        raise RuntimeError(
            f"bench_lm: compiled step holds {mosaic_calls} Mosaic custom "
            f"calls, expected >= {3 * config.num_layers} — flash attention "
            "fell back to the dense reference"
        )

    timing = _timed_steps(step, state, batch, steps)
    tuned_step = autotune.tuned_step_schedule(
        "lm", (batch_size, seq_len), {"data": timing["n_chips"]}, jnp.bfloat16
    )
    tokens_per_s = (
        batch_size * seq_len * timing["steps_per_s"] / timing["n_chips"]
    )
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    # Analytic train FLOPs/token: 6N for the matmul stack (fwd 2N + bwd 4N)
    # plus causal attention scores/values (12·L·S·d_attn, halved triangle,
    # ×3 for fwd+bwd over fwd).
    d_attn = config.num_heads * config.head_dim
    attn_flops = 3 * 4 * config.num_layers * seq_len * d_attn * 0.5
    flops_per_token = 6 * n_params + attn_flops
    tflops = tokens_per_s * flops_per_token / 1e12
    return {
        "seq_len": seq_len,
        "batch_size": batch_size,
        "n_params": n_params,
        **timing,
        "tokens_per_s_per_chip": round(tokens_per_s, 1),
        "achieved_tflops_per_chip": round(tflops, 1),
        "mfu": round(tflops / (device_peak_flops() / 1e12), 3),
        "attention": "pallas_flash_compiled"
        if mosaic_calls
        else "pallas_flash_interpret",
        "mosaic_calls": mosaic_calls,
        "remat": remat,
        "tuned_step": tuned_step,  # DB hit for this shape (informational)
    }


def bench_decode(
    context: int = 2048,
    new_tokens: int = 128,
    batch_sizes: tuple[int, ...] = (1, 8, 32),
) -> dict:
    """Serving throughput on the 110M model with the honest phase split.

    Two separately-jitted, separately-timed phases per batch size:

    - ``prefill_tokens_per_s`` — the batched cache-fill forward over the
      prompt (MXU-bound, flash-kernel path; ``models.generate.prefill``);
    - ``decode_tokens_per_s`` — the continuous single-token decode scan
      over a cache prefilled to ``context - new_tokens``, counting ONLY
      generated tokens (``models.generate.decode_tokens``).

    The round-4 bench decoded every position sequentially (prefill included)
    and reported one blended "positions/s" — mostly prefill, which the
    verdict called flattered. Batch sizes probe the serving roofline: decode
    HBM traffic = weights (220 MB/step, batch-invariant — the batching win)
    + KV cache (~75 MB/step/row at 2k MHA — the batching limit), so
    tokens/s should scale with B sublinearly, approaching bytes-roofline
    ratios, not 1:1 (see docs/PERF_ANALYSIS.md §10 for the model and the
    GQA/window/int8 levers that shrink the cache term).

    Synced by ``host_sync`` like every bench here.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.compiler import aot
    from deeplearning_mpi_tpu.models.generate import decode_tokens, prefill
    from deeplearning_mpi_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    config = TransformerConfig()
    model = TransformerLM(config=config, dtype=jnp.bfloat16)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    p_len = context - new_tokens

    @jax.jit
    def run_prefill(params, prompt):
        return prefill(model, params, prompt, total_len=context)

    @jax.jit
    def run_decode(params, cache, first, rng):
        return decode_tokens(
            model, params, cache, first,
            start=p_len, steps=new_tokens, rng=rng, temperature=0.0,
        )

    result: dict = {
        "context": context,
        "new_tokens": new_tokens,
        "prompt_len": p_len,
        "per_batch": {},
    }
    rng = jax.random.key(0)
    for batch in batch_sizes:
        prompt = jnp.zeros((batch, p_len), jnp.int32)
        # generate.prefill takes the Pallas flash kernel on TPU only, and
        # flash_attention falls back to dense when the prompt does not
        # tile: the prefill rate below must name the schedule that ran.
        prefill_c = run_prefill.lower(params, prompt).compile()
        mosaic_calls = aot.mosaic_call_count(prefill_c)
        if jax.default_backend() == "tpu" and mosaic_calls < config.num_layers:
            raise RuntimeError(
                f"bench_decode: prefill holds {mosaic_calls} Mosaic custom "
                f"calls, expected >= {config.num_layers}"
            )
        cache, logits = prefill_c(params, prompt)  # warm
        host_sync(logits.ravel()[:1])
        t0 = time.perf_counter()
        cache, logits = prefill_c(params, prompt)
        host_sync(logits.ravel()[:1])
        dt_pre = time.perf_counter() - t0

        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks = run_decode(params, cache, first, rng)  # compile + warm
        host_sync(toks.ravel()[:1])
        t0 = time.perf_counter()
        toks = run_decode(params, cache, first, rng)
        host_sync(toks.ravel()[:1])
        dt_dec = time.perf_counter() - t0

        # The decode scan executes new_tokens - 1 model steps (the first
        # generated token is the prefill's sample) — rates divide by what
        # ran, not the tokens returned (an 1/new_tokens flattering bias
        # otherwise; review r5).
        dec_steps = new_tokens - 1
        result["per_batch"][str(batch)] = {
            "prefill_mosaic_calls": mosaic_calls,
            "prefill_ms": round(dt_pre * 1e3, 2),
            "prefill_tokens_per_s": round(batch * p_len / dt_pre, 1),
            "decode_ms_per_step": round(dt_dec / dec_steps * 1e3, 3),
            "decode_tokens_per_s": round(batch * dec_steps / dt_dec, 1),
        }
    return result


def bench_spec_decode(
    context: int = 128,
    new_tokens: int = 96,
    batch: int = 32,
    spec_k: int = 1,
    draft_layers: int = 1,
) -> dict:
    """Speculative + large-batch serving vs the round-4 decode harness.

    Three arms on the SAME ~110M model (byte vocab, the bench_e2e shape):

    - ``single_stream_positions_per_s`` — the r04 harness re-measured in
      this process: ``generate_jit``, one request at a time, blended
      prefill+decode positions/s (the 1,341 baseline's exact recipe, on
      whatever chip THIS round runs on — see R04_SINGLE_STREAM note);
    - ``spec_positions_per_s`` — the paged engine serving ``batch``
      concurrent copies of the workload with chunked prefill, bucketed
      decode batching, and a ``draft_layers``-layer self-draft proposing
      ``spec_k`` tokens per sequence per verify step;
    - ``plain_positions_per_s`` — the same engine with speculation OFF
      (the k=0 candidate ``tools/autotune.py --spec_k`` always races).

    The headline ``positions_per_s`` is the better engine arm — the
    configuration a deploy would pick, and the field measurement of the
    k-vs-0 question ``tune_spec_k`` answers offline (``deployed_spec_k``
    says which won; on a compute-bound CPU host expect 0 — the verify
    step re-spends arithmetic that batching already saturated, see
    docs/PERF_ANALYSIS.md §12). Greedy parity means all arms emit
    identical streams, so the ratios are pure throughput comparisons;
    the measured ``acceptance_rate`` and the proposed/accepted/rollback
    reconciliation ride the details regardless of which arm wins. Engine
    arms are AOT-warmed first (``ServingEngine.warmup``) so the timed
    windows contain zero compiles — same discipline as every bench here.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.compiler import autotune
    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.models.generate import generate_jit
    from deeplearning_mpi_tpu.models.transformer import (
        draft_config,
        truncate_lm_params,
    )
    from deeplearning_mpi_tpu.serving import EngineConfig, ServingEngine
    from deeplearning_mpi_tpu.telemetry import MetricsRegistry
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    cfg = TransformerConfig(
        vocab_size=256, num_layers=12, num_heads=12, head_dim=64,
        d_model=768, d_ff=3072,
    )
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = TransformerLM(config=cfg, dtype=dt)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt_len = context - new_tokens

    # The engine arms: the paged engine, batch concurrent requests — once
    # with the self-draft proposing (spec), once with speculation off
    # (plain: the k=0 candidate the spec-k tuner always keeps in the
    # field). Identical pool geometry in both.
    block_size = 32
    blocks_per_seq = (context + spec_k) // block_size + 2
    base_cfg = dict(
        max_slots=batch,
        block_size=block_size,
        num_blocks=batch * blocks_per_seq + 8,
        max_blocks_per_seq=blocks_per_seq,
        # One chunk covers the whole (short, decode-dominated workload)
        # prompt; a wider fixed-shape chunk would pad-and-waste.
        prefill_chunk=min(64, prompt_len),
        max_queue=2 * batch,
        decode_buckets=(batch // 2, batch) if batch >= 2 else (),
    )

    def run_engine(k: int) -> dict:
        registry = MetricsRegistry()
        draft = dict(
            draft_config=draft_config(cfg, draft_layers),
            draft_params=truncate_lm_params(params, draft_layers),
        ) if k else {}
        engine = ServingEngine(
            cfg, params, EngineConfig(spec_k=k, **base_cfg),
            dtype=dt, registry=registry, **draft,
        )
        engine.warmup()
        nrng = np.random.default_rng(0)
        for _ in range(batch):
            engine.submit(
                nrng.integers(
                    1, cfg.vocab_size, size=prompt_len
                ).astype(np.int32),
                new_tokens,
            )
        t0 = time.perf_counter()
        finished = engine.run_until_idle()
        wall = time.perf_counter() - t0
        positions = sum(r.prompt_len + len(r.generated) for r in finished)
        tokens = sum(len(r.generated) for r in finished)
        return {
            "wall": wall,
            "pps": positions / wall,
            "tokens": tokens,
            "finished": len(finished),
            "snap": registry.snapshot(),
        }

    spec = run_engine(spec_k)
    plain = run_engine(0)
    best = spec if spec["pps"] >= plain["pps"] else plain

    # The baseline arm: the r04 harness, verbatim recipe
    # (tools/bench_decode.py bench_e2e): one stream, jitted generate,
    # blended positions/s. Measured LAST, directly adjacent to the engine
    # arms' timed windows — minutes of sustained load separate process
    # start from here, and measuring the baseline in the cold-turbo window
    # while the engine arms run thermally throttled would bias the ratio
    # AGAINST the engine (observed ~25% single-stream swing on the CPU
    # rig between the first and last minutes of this entry).
    fn = generate_jit(model, max_new_tokens=new_tokens, temperature=0.0)
    rng = jax.random.key(0)
    prompts = [
        jax.random.randint(
            jax.random.key(s), (1, prompt_len), 0, cfg.vocab_size, jnp.int32
        )
        for s in range(4)
    ]
    host_sync(fn(params, prompts[0], rng).ravel()[:1])  # compile
    times = []
    for p in prompts[1:]:
        t0 = time.perf_counter()
        host_sync(fn(params, p, rng).ravel()[:1])
        times.append(time.perf_counter() - t0)
    single_dt = min(times)
    single_pps = context / single_dt

    snap = spec["snap"]
    proposed = snap.get("spec_proposed_total", 0)
    accepted = snap.get("spec_accepted_total", 0)
    rollback = snap.get("spec_rollback_total", 0)
    engine_pps = best["pps"]
    result = {
        "context": context,
        "new_tokens": new_tokens,
        "batch": batch,
        "spec_k": spec_k,
        "draft_layers": draft_layers,
        "requests_finished": best["finished"],
        "single_stream_positions_per_s": round(single_pps, 1),
        "positions_per_s": round(engine_pps, 1),
        "spec_positions_per_s": round(spec["pps"], 1),
        "plain_positions_per_s": round(plain["pps"], 1),
        "deployed_spec_k": spec_k if best is spec else 0,
        "speedup_vs_single_stream": round(engine_pps / single_pps, 2),
        "vs_r04": round(engine_pps / R04_SINGLE_STREAM_POSITIONS_PER_S, 2),
        "r04_note": (
            "r04's 1341 positions/s was measured on TPU v5 lite; "
            "speedup_vs_single_stream re-runs that recipe on THIS host"
        ),
        "generated_tokens_per_s": round(best["tokens"] / best["wall"], 1),
        "accepted_tokens_per_s": round(accepted / spec["wall"], 1),
        "acceptance_rate": round(accepted / proposed, 3) if proposed else None,
        "spec_proposed": int(proposed),
        "spec_accepted": int(accepted),
        "spec_rollback": int(rollback),
        "spec_reconciled": proposed == accepted + rollback,
        "decode_steps": best["snap"].get("serve_decode_steps", 0),
    }
    db = autotune.default_db()
    if db is not None and db.consulted:
        result["tuning_provenance"] = db.consulted
    return result


def bench_allreduce() -> dict:
    """Gradient-sized all-reduce latency over the data axis — the BASELINE.md
    'DDP all-reduce step latency' metric (the reference's unmeasured hot path,
    ``pytorch/resnet/main.py:131``). 0.0 by definition on a 1-chip mesh."""
    from deeplearning_mpi_tpu.runtime.mesh import create_mesh
    from deeplearning_mpi_tpu.utils.profiling import measure_collective_latency

    # 25.6M floats (102.4 MB) = the full ResNet-50 gradient payload; the
    # helper's per-device shard is num_floats elements.
    return measure_collective_latency(create_mesh(), num_floats=25_600_000)


def bench_fleet(replicas: int = 2) -> dict:
    """Failover-recovery latency of the fault-tolerant serving fleet.

    A ``replicas``-worker CPU fleet (``serving/fleet.py``) serves a
    burst+trickle trace while a planned ``replica_kill`` takes one worker
    down mid-decode. The headline is the **failover-recovery latency**:
    detection (exit reaped / progress stall) → every orphaned request
    re-dispatched to a survivor and completed — the ``recovery_latency_s``
    histogram the chaos injector keeps. TTFT p50/p99 before/during/after
    the failure ride along so the latency a client actually sees through
    the failover is visible next to the supervisor-side number.

    The model is deliberately the serve-smoke tiny shape: this entry
    measures the supervision/re-dispatch control plane, not model FLOPs —
    the fleet workers are CPU processes by design (the supervisor is
    host-side policy), so the entry forces ``JAX_PLATFORMS=cpu`` in the
    workers regardless of the bench platform.
    """
    import tempfile

    import numpy as np

    from deeplearning_mpi_tpu.serving import FleetSupervisor

    repo = os.path.dirname(os.path.abspath(__file__))
    model_spec = {
        "vocab_size": 256, "num_layers": 2, "num_heads": 2,
        "num_kv_heads": None, "head_dim": 16, "d_model": 64, "d_ff": 128,
        "attention_window": None,
    }
    engine_spec = {
        "max_slots": 3, "block_size": 8, "num_blocks": 32,
        "max_blocks_per_seq": 6, "prefill_chunk": 8, "max_queue": 64,
    }
    rng = np.random.default_rng(7)
    n_burst, n_trickle, max_new = 12, 12, 6
    entries = []
    for i in range(n_burst + n_trickle):
        n = int(rng.integers(3, 21))
        entries.append({
            "arrival": 0.0 if i < n_burst else (i - n_burst + 1) * 0.08,
            "prompt": [int(t) for t in rng.integers(1, 256, size=n)],
            "max_new": max_new,
            "deadline": 0.0,
        })

    env = {
        "PYTHONPATH": os.pathsep.join(
            p for p in (repo, os.environ.get("PYTHONPATH", "")) if p
        ),
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(repo, ".jax_cache")
        ),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0.3",
    }
    heartbeat_deadline_s = 3.0
    fleet_dir = tempfile.mkdtemp(prefix="dmt_bench_fleet_")
    sup = FleetSupervisor(
        model_spec, engine_spec, replicas, fleet_dir,
        seed=0,
        chaos="replica_kill@step:4",
        heartbeat_interval_s=0.2,
        heartbeat_deadline_s=heartbeat_deadline_s,
        spawn_grace_s=600.0,
        max_replica_restarts=4,
        timeout_s=480.0,
        env=env,
    )
    t0 = time.perf_counter()
    result = sup.run(entries)
    wall = time.perf_counter() - t0
    snap = result.snapshot
    tokens = sum(len(r["tokens"]) for r in result.requests.values())
    return {
        "replicas": replicas,
        "requests": len(entries),
        "completed": result.completed,
        "dropped": result.dropped,
        "redispatched": result.redispatched,
        "restarts": result.restarts,
        # Supervisor-side: detection -> books closed (orphans completed).
        "failover_recovery_s_p50": snap.get("recovery_latency_s_p50"),
        "failover_recovery_s_max": snap.get("recovery_latency_s_max"),
        # Client-side: what the failure did to first-token latency.
        "ttft_before_p50_s": result.ttft.get("before_p50"),
        "ttft_during_p50_s": result.ttft.get("during_p50"),
        "ttft_during_p99_s": result.ttft.get("during_p99"),
        "ttft_after_p50_s": result.ttft.get("after_p50"),
        "detect_budget_s": heartbeat_deadline_s,
        "wall_s": round(wall, 2),
        "generated_tokens_per_s": round(tokens / wall, 1),
        "chaos_balanced": result.chaos_balanced,
        "fleet_ok": result.ok,
    }


def bench_disagg(
    n_burst: int = 12,
    n_trickle: int = 12,
    max_new: int = 6,
) -> dict:
    """Disaggregated prefill/decode serving vs the colocated engine, plus
    the int8 paged-KV capacity multiplier (ISSUE 9).

    Three arms serve the SAME burst+trickle trace (``n_burst`` requests at
    t=0, then ``n_trickle`` more at 80 ms spacing — the bench_fleet arrival
    pattern, replayed in real time against the engine's monotonic clock):

    - ``colocated`` — the single ``ServingEngine``, fp KV: the reference
      streams and the TTFT baseline;
    - ``disagg`` — ``DisaggregatedEngine`` (prefill-only engine handing
      finished prompts to a decode-only engine over one shared pool), fp
      KV. Greedy decode is batch-invariant, so these streams must be
      BIT-identical to the colocated arm's — the split topology is judged
      purely on latency (``ttft_p99_ratio_vs_colocated``: the headline
      claim is that isolating prefill keeps decode's cadence, and
      therefore tail TTFT under burst, no worse than colocated);
    - ``disagg_int8`` — the same topology with the opt-in int8 paged KV
      cache. Lossy by design, so it is judged the way the CLI gate judges
      it: matched-prefix token acceptance against the fp reference
      (greedy forks permanently at the first divergence), plus the
      capacity multiplier below.

    The int8 headline is ``resident_seqs_x``: at a FIXED HBM byte budget,
    how many more sequences stay resident when a KV token costs
    2·H_kv·D int8 bytes + 2·H_kv f32 scales instead of 2·H_kv·D fp bytes.
    Both the analytic per-token numbers and the measured buffer bytes of
    the two arms (same pool geometry) ride the details; the acceptance
    bar for the ISSUE is >= 1.9x (see docs/PERF_ANALYSIS.md §13 for why
    the smoke shape lands at 3.2x and a production GQA shape at ~3.6x).

    The model is the serve-smoke tiny shape: like bench_fleet, this entry
    measures scheduling/topology (handoff latency, admission under burst),
    not model FLOPs. All arms are AOT-warmed; timed windows contain zero
    compiles.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.serving import (
        DisaggregatedEngine,
        EngineConfig,
        ServingEngine,
    )
    from deeplearning_mpi_tpu.telemetry import MetricsRegistry

    cfg = TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=2, head_dim=16,
        d_model=64, d_ff=128,
    )
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = TransformerLM(config=cfg, dtype=dt)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    base = EngineConfig(
        max_slots=3, block_size=8, num_blocks=32, max_blocks_per_seq=6,
        prefill_chunk=8, max_queue=64,
    )

    rng = np.random.default_rng(7)
    trace = []
    for i in range(n_burst + n_trickle):
        n = int(rng.integers(3, 21))
        trace.append((
            0.0 if i < n_burst else (i - n_burst + 1) * 0.08,
            rng.integers(1, cfg.vocab_size, size=n).astype(np.int32),
        ))

    def pct(xs: list, q: float) -> float | None:
        return round(float(np.percentile(xs, q)), 4) if xs else None

    def run_arm(disagg: bool, kv_dtype: str | None) -> tuple[dict, list]:
        registry = MetricsRegistry()
        cls = DisaggregatedEngine if disagg else ServingEngine
        engine = cls(
            cfg, params,
            dataclasses.replace(base, kv_dtype=kv_dtype),
            dtype=dt, registry=registry,
        )
        engine.warmup()
        idle = engine.idle if disagg else engine.scheduler.idle
        reqs, pending = [], list(trace)
        t0 = time.monotonic()
        while pending or not idle():
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                arr, prompt = pending.pop(0)
                reqs.append(engine.submit(prompt, max_new, arrival=t0 + arr))
            if not idle():
                engine.step()
            elif pending:  # trace gap: engine drained ahead of the trickle
                gap = pending[0][0] - (time.monotonic() - t0)
                if gap > 0:
                    time.sleep(gap)
        wall = time.monotonic() - t0
        snap = registry.snapshot()
        done = [r for r in reqs if r.t_finished is not None]
        ttfts = sorted(r.ttft for r in done if r.ttft is not None)
        tpots = sorted(r.tpot for r in done if r.tpot is not None)
        tokens = sum(len(r.generated) for r in done)
        detail = {
            "requests_finished": len(done),
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "tpot_p50_s": pct(tpots, 50),
            "wall_s": round(wall, 2),
            "generated_tokens_per_s": round(tokens / wall, 1),
            "decode_steps": snap.get("serve_decode_steps", 0),
        }
        if disagg:
            detail["handoffs"] = snap.get("serve_handoffs_total", 0)
        for key, val in snap.items():  # measured KV buffer bytes, by dtype
            if key.startswith('serve_kv_bytes{dtype='):
                detail["kv_bytes"] = {key.split('"')[1]: int(val)}
        streams = [
            [int(t) for t in r.generated]
            for r in sorted(done, key=lambda r: r.rid)
        ]
        return detail, streams

    colo, ref_streams = run_arm(False, None)
    disagg, disagg_streams = run_arm(True, None)
    int8, int8_streams = run_arm(True, "int8")

    # int8 acceptance: matched-prefix tokens vs the fp reference (greedy
    # forks permanently at the first divergence) — the same rule the CLI
    # --kv_acceptance_min gate applies.
    expected = accepted = 0
    for ref, got in zip(ref_streams, int8_streams):
        agree = 0
        for a, b in zip(ref, got):
            if a != b:
                break
            agree += 1
        expected += len(ref)
        accepted += agree

    # Capacity at a fixed byte budget: bytes one KV token costs per layer.
    hkv = cfg.num_kv_heads or cfg.num_heads
    fp_tok = 2 * hkv * cfg.head_dim * jnp.dtype(dt).itemsize
    int8_tok = 2 * hkv * cfg.head_dim * 1 + 2 * hkv * 4  # int8 q + f32 scale
    resident_x = fp_tok / int8_tok

    result = {
        "requests": len(trace),
        "burst": n_burst,
        "trickle": n_trickle,
        "max_new": max_new,
        "colocated": colo,
        "disagg": disagg,
        "disagg_int8": int8,
        "disagg_bit_identical_to_colocated": disagg_streams == ref_streams,
        "ttft_p99_ratio_vs_colocated": (
            round(disagg["ttft_p99_s"] / colo["ttft_p99_s"], 2)
            if disagg["ttft_p99_s"] and colo["ttft_p99_s"] else None
        ),
        "int8_acceptance_rate": (
            round(accepted / expected, 3) if expected else None
        ),
        "kv_bytes_per_token_per_layer": {
            str(jnp.dtype(dt)): fp_tok, "int8": int8_tok,
        },
        # At a fixed pool byte budget, int8 keeps resident_seqs_x more
        # sequences' KV resident than the fp cache (ISSUE bar: >= 1.9x).
        "resident_seqs_x": round(resident_x, 2),
    }
    from deeplearning_mpi_tpu.compiler import autotune

    db = autotune.default_db()
    if db is not None and db.consulted:
        result["tuning_provenance"] = db.consulted
    return result


def bench_serving_prefix(
    n_burst: int = 16,
    n_trickle: int = 8,
    preamble_len: int = 34,
    tail_len: int = 8,
    max_new: int = 6,
) -> dict:
    """Radix prefix cache vs the cacheless engine on a shared-preamble
    trace (ISSUE 12).

    Both arms serve the SAME burst+trickle trace (``n_burst`` requests at
    t=0, then ``n_trickle`` at 80 ms spacing): every prompt is one shared
    ``preamble_len``-token preamble plus a unique ``tail_len``-token tail
    — the "same system prompt, different question" shape, ~80% of each
    prompt shared. The preamble is deliberately NOT block-aligned, so
    every adoption also pays a copy-on-write block copy (the honest cost).

    - ``no_cache`` — the plain ``ServingEngine``: reference streams and
      the TTFT baseline. Every admission re-prefills all
      ``preamble_len + tail_len`` tokens.
    - ``prefix_cache`` — the same engine with the radix cache on: after
      the first completed prefill the preamble's KV blocks are adopted by
      reference and only the tail (plus one CoW copy) is computed.

    Headline is ``prefill_tokens_reduction_x`` = prompt tokens submitted /
    prompt tokens actually prefilled (submitted − reused); the ISSUE bar
    is >= 2x at 80% sharing. ``ttft_p99_ratio_vs_no_cache`` must come in
    < 1.0 — skipped prefill work is queue time the burst's tail never
    waits for. Greedy decode is deterministic and adopted blocks hold
    bit-equal KV (same tokens, same params), so the streams must be
    BIT-identical between arms — the cache is judged on latency, never
    allowed to shift tokens. Like bench_fleet/bench_disagg this measures
    scheduling (admission, adoption, CoW), not model FLOPs: the model is
    the serve-smoke tiny shape, AOT-warmed, zero compiles in the timed
    window.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.serving import EngineConfig, ServingEngine
    from deeplearning_mpi_tpu.telemetry import MetricsRegistry

    cfg = TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=2, head_dim=16,
        d_model=64, d_ff=128,
    )
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = TransformerLM(config=cfg, dtype=dt)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    base = EngineConfig(
        max_slots=3, block_size=8, num_blocks=64, max_blocks_per_seq=6,
        prefill_chunk=8, max_queue=64,
    )

    rng = np.random.default_rng(7)
    preamble = rng.integers(1, cfg.vocab_size, size=preamble_len).astype(
        np.int32
    )
    trace = []
    for i in range(n_burst + n_trickle):
        tail = rng.integers(1, cfg.vocab_size, size=tail_len).astype(np.int32)
        trace.append((
            0.0 if i < n_burst else (i - n_burst + 1) * 0.08,
            np.concatenate([preamble, tail]),
        ))
    prompt_tokens = sum(len(p) for _, p in trace)

    def pct(xs: list, q: float) -> float | None:
        return round(float(np.percentile(xs, q)), 4) if xs else None

    def run_arm(cached: bool) -> tuple[dict, list]:
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg, params,
            dataclasses.replace(base, prefix_cache=cached),
            dtype=dt, registry=registry,
        )
        engine.warmup()
        reqs, pending = [], list(trace)
        t0 = time.monotonic()
        while pending or not engine.scheduler.idle():
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                arr, prompt = pending.pop(0)
                reqs.append(engine.submit(prompt, max_new, arrival=t0 + arr))
            if not engine.scheduler.idle():
                engine.step()
            elif pending:
                gap = pending[0][0] - (time.monotonic() - t0)
                if gap > 0:
                    time.sleep(gap)
        wall = time.monotonic() - t0
        snap = registry.snapshot()
        done = [r for r in reqs if r.t_finished is not None]
        ttfts = sorted(r.ttft for r in done if r.ttft is not None)
        reused = int(snap.get("serve_prefix_tokens_reused_total", 0))
        detail = {
            "requests_finished": len(done),
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "wall_s": round(wall, 2),
            "prompt_tokens": prompt_tokens,
            "prefilled_tokens": prompt_tokens - reused,
            "prefix_hits": int(snap.get("serve_prefix_hits_total", 0)),
            "prefix_tokens_reused": reused,
            "cow_copies": int(snap.get("serve_prefix_cow_copies_total", 0)),
            "evictions": int(snap.get("serve_prefix_evictions_total", 0)),
        }
        streams = [
            [int(t) for t in r.generated]
            for r in sorted(done, key=lambda r: r.rid)
        ]
        return detail, streams

    cold, ref_streams = run_arm(False)
    warm, warm_streams = run_arm(True)

    result = {
        "requests": len(trace),
        "burst": n_burst,
        "trickle": n_trickle,
        "shared_fraction": round(preamble_len / (preamble_len + tail_len), 2),
        "max_new": max_new,
        "no_cache": cold,
        "prefix_cache": warm,
        "bit_identical_to_no_cache": warm_streams == ref_streams,
        # Prompt tokens submitted / prompt tokens actually prefilled: how
        # much prefill compute adoption removed (ISSUE bar: >= 2x at ~80%
        # sharing; the first request of each branch is always cold).
        "prefill_tokens_reduction_x": (
            round(prompt_tokens / warm["prefilled_tokens"], 2)
            if warm["prefilled_tokens"] else None
        ),
        "ttft_p99_ratio_vs_no_cache": (
            round(warm["ttft_p99_s"] / cold["ttft_p99_s"], 2)
            if warm["ttft_p99_s"] and cold["ttft_p99_s"] else None
        ),
    }
    from deeplearning_mpi_tpu.compiler import autotune

    db = autotune.default_db()
    if db is not None and db.consulted:
        result["tuning_provenance"] = db.consulted
    return result


def bench_slo_curves(duration_s: float = 600.0, base_rps: float = 6.0
                     ) -> dict:
    """Predictive vs reactive autoscaling on a flash-crowd day through the
    fake-clock fleet simulator (ISSUE 19) — pure host Python, no device.

    Both arms replay the SAME seeded trace (diurnal cycle + one
    ramp-onset flash crowd) through the REAL router/scheduler/autoscaler
    objects; the only difference is ``AutoscalerConfig.predictive``. The
    regime is continuously loaded (slow decodes, long outputs, a fleet
    sized near saturation) — the one where a trend forecast has signal to
    lead with; an idle fleet's 0-to-avalanche step gives the forecaster
    nothing and the arms tie by construction.

    Headline is ``predictive_slo_per_chip_x``: SLO-attained completions
    per replica-second, predictive over reactive — the sweep's scoring
    metric, so this number and ``sim/search.py`` winners are directly
    comparable. Per-arm SLO attainment, sheds, scale-up stamps, and the
    windowed SLO/utilization curves ride in the detail dict.
    """
    from deeplearning_mpi_tpu.serving.autoscaler import AutoscalerConfig
    from deeplearning_mpi_tpu.sim import (
        FlashCrowd,
        FleetSimulator,
        ServiceModel,
        SimConfig,
        TenantSpec,
        TraceConfig,
        generate_entries,
        to_fleet_entries,
        trace_digest,
    )

    cfg = TraceConfig(
        duration_s=duration_s,
        base_rps=base_rps,
        diurnal_period_s=duration_s,
        diurnal_amplitude=0.3,
        burst_rate_per_s=0.0,
        flash_crowds=(
            FlashCrowd(at_s=duration_s * 0.6, amplitude=6.0, ramp_s=12.0,
                       decay_s=8.0),
        ),
        tenants=(
            TenantSpec("default", output_mean=32, deadline_s=10.0),
        ),
    )
    entries = to_fleet_entries(generate_entries(cfg, seed=0))

    def arm(predictive: bool) -> dict:
        sim_cfg = SimConfig(
            initial_replicas=3,
            max_slots=4,
            service=ServiceModel(tpot_s=0.05),
            autoscale=AutoscalerConfig(
                min_replicas=2, max_replicas=8,
                up_load_per_replica=6.0, down_load_per_replica=1.0,
                hysteresis_s=0.4, cooldown_s=2.0,
                predictive=predictive, forecast_horizon_s=3.0,
                forecast_tau_s=1.0, forecast_trend_tau_s=2.0,
            ),
            curve_window_s=30.0,
        )
        t0 = time.monotonic()
        res = FleetSimulator(sim_cfg).run(entries)
        return {
            "slo_attainment": round(res.slo_attainment, 4),
            "slo_per_chip": round(res.slo_per_chip, 4),
            "completed": res.completed,
            "shed": dict(res.shed),
            "scale_ups": res.scale_ups,
            "first_up_s": round(res.up_times[0], 2) if res.up_times
            else None,
            "replica_seconds": round(res.replica_seconds, 1),
            "wall_s": round(time.monotonic() - t0, 2),
            "curves": res.curves,
        }

    reactive = arm(False)
    predictive = arm(True)
    return {
        "requests": len(entries),
        "trace_digest": trace_digest(entries),
        "predictive_slo_per_chip_x": (
            round(predictive["slo_per_chip"] / reactive["slo_per_chip"], 4)
            if reactive["slo_per_chip"] else None
        ),
        "predictive_slo_attainment_delta": round(
            predictive["slo_attainment"] - reactive["slo_attainment"], 4
        ),
        "reactive": reactive,
        "predictive": predictive,
    }


def _kill_group(proc) -> None:
    """SIGKILL a child's whole process group, then reap it. The child may
    spawn helpers (fleet workers) that inherit the pipes; killing only the
    child would leave communicate() blocked on pipe EOF — the budget guard
    must not hang."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.communicate()


def _combined_line(details: dict, error: str | None = None) -> str:
    """The ONE final JSON line the driver parses, derived purely from
    ``details`` so it can always be emitted with whatever sub-benches
    completed — a failed workload contributes a ``{"failed": ...}`` entry
    whose headline values degrade to null, never a missing line."""
    r224 = details.get("imagenet_224px") or {}
    r32 = details.get("cifar_32px") or {}
    value = r224.get("images_per_s_per_chip") or r32.get("images_per_s_per_chip")
    lm = details.get("transformer_lm_2k_flash") or {}
    unet = details.get("unet2d_512px") or {}
    serving = (details.get("lm_serving_2k") or {}).get("per_batch", {})
    spec = details.get("lm_spec_decode") or {}
    fleet = details.get("serving_fleet") or {}
    disagg = details.get("serving_disagg") or {}
    prefix = details.get("serving_prefix") or {}
    allreduce = details.get("allreduce") or {}
    out = {
        "metric": "resnet50_bf16_images_per_sec_per_chip",
        "value": round(value, 1) if value is not None else None,
        "unit": "images/s/chip",
        "vs_baseline": round(value / A100_RESNET50_224_IMG_PER_S, 3)
        if value is not None
        else None,
        "mfu": r224.get("mfu"),
        "lm_tokens_per_s": lm.get("tokens_per_s_per_chip"),
        "lm_mfu": lm.get("mfu"),
        "unet_images_per_s": unet.get("images_per_s_per_chip"),
        # Serving headline, split honestly (round-4 verdict #1): prefill is
        # the batched cache-fill forward; decode counts generated tokens
        # only, at batch 1 and batched.
        "prefill_tokens_per_s_b8": (serving.get("8") or {}).get(
            "prefill_tokens_per_s"
        ),
        "decode_tokens_per_s_b1": (serving.get("1") or {}).get(
            "decode_tokens_per_s"
        ),
        "decode_tokens_per_s_b8": (serving.get("8") or {}).get(
            "decode_tokens_per_s"
        ),
        "decode_tokens_per_s_b32": (serving.get("32") or {}).get(
            "decode_tokens_per_s"
        ),
        # Speculative + large-batch serving headline (ISSUE 7): blended
        # positions/s at batch >= 8 against the single-stream r04 harness
        # re-measured in the same process, plus the measured draft
        # acceptance rate.
        "spec_decode_positions_per_s": spec.get("positions_per_s"),
        "spec_speedup_vs_single_stream": spec.get(
            "speedup_vs_single_stream"
        ),
        "spec_acceptance_rate": spec.get("acceptance_rate"),
        # Fleet robustness headline (ISSUE 8): detection -> orphans
        # completed on a survivor, and the client-visible TTFT hit.
        "fleet_failover_recovery_s": fleet.get("failover_recovery_s_p50"),
        "fleet_ttft_during_p99_s": fleet.get("ttft_during_p99_s"),
        # Disaggregated prefill/decode + int8 KV headline (ISSUE 9): tail
        # TTFT of the split topology relative to colocated on the same
        # burst+trickle trace (<= 1.0 means no worse), and the int8 cache's
        # resident-sequence multiplier at a fixed byte budget with its
        # measured token-level acceptance vs the fp reference.
        "disagg_ttft_p99_vs_colocated": disagg.get(
            "ttft_p99_ratio_vs_colocated"
        ),
        "kv_int8_resident_seqs_x": disagg.get("resident_seqs_x"),
        "kv_int8_acceptance_rate": disagg.get("int8_acceptance_rate"),
        # Radix prefix cache headline (ISSUE 12): prefill compute removed
        # by KV adoption on an ~80%-shared-preamble trace (>= 2x bar) and
        # the client-visible tail-TTFT ratio vs the cacheless arm (< 1.0
        # means the saved prefill reached the client).
        "prefix_prefill_tokens_reduction_x": prefix.get(
            "prefill_tokens_reduction_x"
        ),
        "prefix_ttft_p99_ratio": prefix.get("ttft_p99_ratio_vs_no_cache"),
        "allreduce_latency_ms": allreduce.get("all_reduce_ms_mean"),
        "details": details,
    }
    if error is not None:
        out["error"] = error
    return json.dumps(out)


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch_224", type=int, default=128)
    parser.add_argument("--batch_32", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--skip_224", action="store_true")
    parser.add_argument("--skip_lm", action="store_true")
    parser.add_argument("--skip_unet", action="store_true")
    parser.add_argument("--skip_decode", action="store_true")
    parser.add_argument("--skip_spec", action="store_true",
                        help="skip the speculative+batched serving workload")
    parser.add_argument("--skip_fleet", action="store_true",
                        help="skip the serving-fleet failover workload")
    parser.add_argument("--skip_disagg", action="store_true",
                        help="skip the disaggregated prefill/decode + "
                        "int8 KV workload")
    parser.add_argument("--skip_prefix", action="store_true",
                        help="skip the radix prefix-cache shared-preamble "
                        "workload")
    parser.add_argument("--skip_slo", action="store_true",
                        help="skip the simulator SLO-curves A/B workload")
    parser.add_argument("--spec_batch", type=int, default=32,
                        help="concurrent requests in the lm_spec_decode "
                        "engine arm (the >=5x target holds for 8-32)")
    parser.add_argument("--long_context", action="store_true",
                        help="add the 32k flash+remat AND 64k "
                        "flash+remat+chunked-loss LM entries (each a "
                        "multi-minute compile; see their call sites)")
    parser.add_argument("--workload_timeout", type=float, default=600.0,
                        help="per-workload wall-clock budget (s); an "
                        "overrunning workload's child process group is "
                        "killed and recorded as a failed entry — the other "
                        "workloads and the final combined line still run; "
                        "the exit code is then non-zero")
    parser.add_argument("--platform", default="tpu", choices=("cpu", "tpu"),
                        help="JAX platform every cell is pinned to and "
                        "asserts; tpu (the default) makes a chip that fails "
                        "to initialise a failed cell, never a quiet CPU run. "
                        "cpu is for debugging the harness only")
    parser.add_argument("--tuning_db", default=None, metavar="PATH",
                        help="tuning DB (JSON from tools/autotune.py) to "
                        "install process-wide; every kernel and step|... "
                        "entry consulted during the run is recorded into the "
                        "final line's details.tuning_provenance")
    parser.add_argument("--only", default=None, metavar="WORKLOAD",
                        help="child mode (internal): run exactly this "
                        "workload in-process and print its detail dict as "
                        "the final JSON line")
    return parser


def _child_main(args) -> int:
    """``--only`` mode: run ONE workload in this process and print its
    detail dict as the LAST stdout line. The parent owns isolation (budget,
    process-group kill); this process just computes. JAX is imported only
    here — a chip belongs to one process at a time, so a parent that had
    touched jax would hold the chip its children need."""
    import jax

    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform(args.platform)
    device = jax.devices()[0]
    if device.platform != args.platform:
        print(f"asked for platform {args.platform!r}, jax gave "
              f"{device.platform!r}", file=sys.stderr)
        return 1
    if args.tuning_db:
        from deeplearning_mpi_tpu.compiler import autotune

        autotune.set_default_db(args.tuning_db)

    key = args.only
    if key == "cifar_32px":
        detail = bench_train_step(32, args.batch_32, args.steps)
    elif key == "imagenet_224px":
        detail = bench_train_step(224, args.batch_224, args.steps)
    elif key == "transformer_lm_2k_flash":
        detail = bench_lm(steps=max(args.steps // 2, 5))
    elif key == "transformer_lm_32k_flash_remat":
        detail = bench_lm(seq_len=32768, batch_size=1, steps=3, remat=True)
    elif key == "transformer_lm_64k_flash_remat_chunked":
        detail = bench_lm(seq_len=65536, batch_size=1, steps=3, remat=True,
                          loss_chunk=2048)
    elif key == "unet2d_512px":
        detail = bench_unet(steps=max(args.steps // 2, 5))
    elif key == "lm_serving_2k":
        detail = bench_decode()
    elif key == "lm_spec_decode":
        detail = bench_spec_decode(batch=args.spec_batch)
    elif key == "serving_fleet":
        detail = bench_fleet()
    elif key == "serving_disagg":
        detail = bench_disagg()
    elif key == "serving_prefix":
        detail = bench_serving_prefix()
    elif key == "serving_slo_curves":
        detail = bench_slo_curves()
    elif key == "allreduce":
        detail = bench_allreduce()
    else:
        print(f"unknown workload '{key}'", file=sys.stderr)
        return 2

    # Per-child tuning provenance rides the sentinel so the parent can
    # aggregate consults across isolated processes.
    from deeplearning_mpi_tpu.compiler import autotune

    db = autotune.default_db()
    if db is not None and db.consulted and "tuning_provenance" not in detail:
        detail["tuning_provenance"] = db.consulted
    # Every result names the device it ran on, so a CPU timing can never be
    # read as a chip number.
    detail.update(
        platform=device.platform, device_kind=device.device_kind,
        device_count=jax.device_count(),
    )
    print(json.dumps({"workload": key, "detail": detail}), flush=True)
    return 0


def _run_isolated(key: str, argv: list[str], budget_s: float) -> dict:
    """Run one workload as ``bench.py --only <key>`` in its own process
    group under a wall-clock budget.

    A JAX call blocked inside a compile ignores signals and cannot be
    interrupted in-process; a child process group can always be killed, so
    an overrun costs exactly one ``{"failed": ...}`` entry and the remaining
    workloads still run. Sequential children also give each cell the chip
    to itself. Returns the workload's detail dict, or the failed entry.
    """
    cmd = [sys.executable, os.path.abspath(__file__), "--only", key, *argv]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, text=True, start_new_session=True,
    )  # stderr inherits: compile/progress noise stays live on the console
    try:
        stdout, _ = proc.communicate(timeout=budget_s)
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        return {
            "failed": f"workload exceeded {budget_s:.0f}s budget; "
            "child process group killed",
        }
    lines = [ln for ln in (stdout or "").splitlines() if ln.strip()]
    sentinel = None
    if lines:
        try:
            parsed = json.loads(lines[-1])
            if parsed.get("workload") == key:
                sentinel = parsed["detail"]
                lines = lines[:-1]
        except (json.JSONDecodeError, AttributeError):
            pass
    for ln in lines:  # re-emit the child's progress lines in order
        print(ln, flush=True)
    if proc.returncode != 0 or sentinel is None:
        return {
            "failed": f"workload exited {proc.returncode} without a "
            "result line",
        }
    return sentinel


def main(argv: list[str] | None = None) -> int:
    child_argv = sys.argv[1:] if argv is None else list(argv)
    args = _build_parser().parse_args(child_argv)
    if args.only:
        return _child_main(args)

    # The parent is a pure orchestrator: it never imports JAX (it would take
    # the chip from its children). Each workload runs in an isolated child
    # under its budget. One JSON line per workload as it completes, then ONE
    # final combined line — the driver parses the LAST line, so every
    # headline number (ResNet, LM, UNet, allreduce) rides it at TOP level:
    # the LM flagship must not be buried inside `details` (round-3 verdict
    # weak #1). Any failed workload makes the exit code non-zero.
    details: dict = {}

    def run(key: str, *, metric: str, unit: str, value_key: str,
            budget_s: float | None = None):
        r = _run_isolated(key, child_argv, budget_s or args.workload_timeout)
        details[key] = r
        if "failed" in r:
            print(json.dumps({"metric": metric, "value": None, "unit": unit,
                              "error": r["failed"]}), flush=True)
            return None
        print(json.dumps({
            "metric": metric, "value": r.get(value_key), "unit": unit,
            "platform": r.get("platform"),
            "device_kind": r.get("device_kind"),
            "device_count": r.get("device_count"),
        }), flush=True)
        return r

    run(
        "cifar_32px",
        metric="resnet50_bf16_cifar32_images_per_sec_per_chip",
        unit="images/s/chip", value_key="images_per_s_per_chip",
    )
    if not args.skip_224:
        run(
            "imagenet_224px",
            metric="resnet50_bf16_224px_images_per_sec_per_chip",
            unit="images/s/chip", value_key="images_per_s_per_chip",
        )

    if not args.skip_lm:
        run(
            "transformer_lm_2k_flash",
            metric="transformer_lm_110m_2k_flash_tokens_per_sec_per_chip",
            unit="tokens/s/chip", value_key="tokens_per_s_per_chip",
        )

    if args.long_context:
        # Long-context proof: 32k tokens through the same 110M model on
        # ONE chip — a config where dense attention cannot even compile
        # (the [S, S] scores alone would be 4 GB); flash + remat make it
        # an ordinary training step. Opt-in: the 32k compile alone takes
        # minutes, which would push the default bench past the driver's
        # window, and the default per-workload budget would kill a healthy
        # 32k/64k compile. Builders' figure on v5e (BASELINE.md): 2,090
        # ms/step = 15.7k tokens/s/chip (16k seq: 26.9k).
        run(
            "transformer_lm_32k_flash_remat",
            metric="transformer_lm_110m_32k_flash_remat_tokens_per_sec_per_chip",
            unit="tokens/s/chip", value_key="tokens_per_s_per_chip",
            budget_s=max(args.workload_timeout, 2400.0),
        )
        # 64k: all three walls at once (flash + remat + chunked head+loss).
        # Measured 2026-07-31: 8.6k tok/s, 7.59 s/step (32k vocab; the
        # byte-vocab CLI variant of the same shape runs 11.0k).
        run(
            "transformer_lm_64k_flash_remat_chunked",
            metric="transformer_lm_110m_64k_flash_remat_chunk_tokens_per_sec_per_chip",
            unit="tokens/s/chip", value_key="tokens_per_s_per_chip",
            budget_s=max(args.workload_timeout, 2400.0),
        )

    if not args.skip_unet:
        run(
            "unet2d_512px",
            metric="unet2d_512px_images_per_sec_per_chip",
            unit="images/s/chip", value_key="images_per_s_per_chip",
        )

    if not args.skip_decode:
        r = run(
            "lm_serving_2k",
            metric="lm_110m_serving_split", unit="tokens/s",
            value_key="new_tokens",  # progress line only; real values below
            # 3 batch sizes x 2 compiles each.
            budget_s=max(args.workload_timeout, 900.0),
        )
        if r:
            print(json.dumps({
                "metric": "lm_110m_decode_tokens_per_sec",
                "value": {
                    b: v.get("decode_tokens_per_s")
                    for b, v in r["per_batch"].items()
                },
                "prefill_tokens_per_s": {
                    b: v.get("prefill_tokens_per_s")
                    for b, v in r["per_batch"].items()
                },
                "unit": "tokens/s by batch",
            }), flush=True)

    if not args.skip_spec:
        run(
            "lm_spec_decode",
            metric="lm_110m_spec_decode_positions_per_sec",
            unit="positions/s", value_key="positions_per_s",
            # Engine warmup + two arms' compiles.
            budget_s=max(args.workload_timeout, 1800.0),
        )

    if not args.skip_fleet:
        run(
            "serving_fleet",
            metric="serving_fleet_failover_recovery_s", unit="s",
            value_key="failover_recovery_s_p50",
            # 2 worker processes each paying a (cached) warmup compile,
            # plus one respawn after the planned kill.
            budget_s=max(args.workload_timeout, 900.0),
        )

    if not args.skip_disagg:
        run(
            "serving_disagg",
            metric="serving_disagg_int8_resident_seqs_x", unit="x",
            value_key="resident_seqs_x",
            # 3 engine arms (colocated, disagg, disagg+int8), each paying
            # a (cached) warmup compile before its timed replay.
            budget_s=max(args.workload_timeout, 900.0),
        )

    if not args.skip_prefix:
        run(
            "serving_prefix",
            metric="serving_prefix_prefill_tokens_reduction_x", unit="x",
            value_key="prefill_tokens_reduction_x",
            # 2 engine arms (no_cache, prefix_cache), each paying a
            # (cached) warmup compile before its timed replay.
            budget_s=max(args.workload_timeout, 900.0),
        )

    if not args.skip_slo:
        # Pure host Python (the fake-clock simulator never touches the
        # device): measures policy quality, not FLOPs.
        run(
            "serving_slo_curves",
            metric="serving_predictive_slo_per_chip_x", unit="x",
            value_key="predictive_slo_per_chip_x",
        )

    run(
        "allreduce",
        metric="allreduce_latency_ms", unit="ms",
        value_key="all_reduce_ms_mean",
    )

    # Which tuning-DB entries the children actually consulted (kernel block
    # shapes, decode buckets, step|... schedules), each with the stored
    # params — so a BENCH_*.json number can be traced back to the autotune
    # results that shaped it. Children report their own consults in their
    # sentinel lines; the parent (JAX-free) only aggregates.
    provenance: list = []
    seen: set = set()
    for r in details.values():
        if isinstance(r, dict):
            for rec in r.get("tuning_provenance") or []:
                if rec.get("key") not in seen:
                    seen.add(rec.get("key"))
                    provenance.append(rec)
    if provenance:
        details["tuning_provenance"] = provenance

    failed = sorted(
        k for k, r in details.items() if isinstance(r, dict) and "failed" in r
    )
    print(_combined_line(
        details, error=f"failed workloads: {failed}" if failed else None
    ))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
