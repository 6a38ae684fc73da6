"""KV-cached decode micro-bench: windowed decode_attention vs the dense
whole-buffer formulation, plus end-to-end generate throughput.

Round-4 evidence for `ops.attention.decode_attention` (the flash-decoding
schedule replacing the dense full-buffer softmax that was
`models/transformer.py`'s one kernel-less attention path): per-token decode
attention at several fill levels of a 2k buffer — the dense path's cost is
constant in the fill (it always reads all max_len rows), the windowed path's
cost tracks the filled prefix — and `generate()` tok/s on a ~110M LM at 2k
context. Timings sync via ``host_sync``; each TPU invocation is one bounded
compile + short loop.

``--spec`` adds the speculative + large-batch serving arm
(``bench.bench_spec_decode``): the paged engine at batch N with a
truncated self-draft vs the single-stream ``--e2e`` harness, reporting
positions/s, accepted-tokens/s, the measured acceptance rate, and the
consulted decode-bucket tuning entries. Its LAST stdout line is the same
combined-JSON schema ``bench.py`` emits, so downstream consumers parse
both tools identically.

Usage: python tools/bench_decode.py [--max_len 2048] [--e2e] [--spec]
       [--tuning_db tuned.json] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def bench_attention(max_len: int, fills: list[int], *, batch: int, heads: int,
                    head_dim: int, kv_heads: int = 0,
                    steps: int = 50, window: int = 0,
                    kernel: bool = False) -> list[dict]:
    """Per-token decode attention: dense-masked vs windowed, same inputs.

    ``kv_heads`` (GQA) sizes the K/V buffers at fewer heads than the query;
    the dense comparator then scores ``repeat_kv``'d buffers (it has no
    grouped form — exactly why the HBM win exists), while the windowed path
    reads the grouped buffers natively.

    ``window`` adds a third arm: the SLIDING-WINDOW walk (``--attention_window``
    models), whose per-token time should be flat in the fill — it starts at
    the window's first cache block, so reads are O(window) however deep the
    generation. (Naming note: "windowed" in this tool's output predates the
    sliding-window feature and means the blockwise prefix walk.)
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from deeplearning_mpi_tpu.ops.attention import (
        NEG_INF,
        decode_attention,
        repeat_kv,
    )
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    kv_heads = kv_heads or heads
    if heads % kv_heads:
        raise ValueError(
            f"--num_kv_heads ({kv_heads}) must divide --heads ({heads})"
        )
    rep = heads // kv_heads
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    q = jax.random.normal(kq, (batch, 1, heads, head_dim), dt)
    k_buf = jax.random.normal(kk, (batch, max_len, kv_heads, head_dim), dt)
    v_buf = jax.random.normal(kv, (batch, max_len, kv_heads, head_dim), dt)

    @jax.jit
    def dense(q, k_buf, v_buf, i):
        # The formulation this tool exists to retire: score the whole
        # buffer, mask the future (pre-round-4 _cached_attention).
        scale = head_dim**-0.5
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_buf, preferred_element_type=jnp.float32
        ) * scale
        valid = jnp.arange(max_len)[None, None, None, :] <= i
        s = jnp.where(valid, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v_buf)

    # dense_max=0 forces the blockwise walk — this tool MEASURES the two
    # schedules against each other, so the dispatcher that normally picks
    # one must not reroute the windowed arm to dense. block=512 matches the
    # recorded PERF_ANALYSIS §9 table (the shipped walk uses 2048).
    windowed = functools.partial(decode_attention, block=512, dense_max=0)
    sliding = (
        functools.partial(
            decode_attention, block=512, dense_max=0, window=window
        )
        if window
        else None
    )
    # Fourth arm (--kernel): the fused Pallas decode kernel — the
    # measurement that decides whether decode_attention's auto-select
    # flips it on (ops/attention.py use_kernel docstring). Refuse lengths
    # the kernel can't tile instead of silently timing the walk fallback
    # under the kernel's name; and add a SHIPPED-config walk arm
    # (block=2048) so kernel_vs_walk compares against what the dispatcher
    # would actually replace, not the block=512 measurement arm.
    fused = shipped_walk = fused_q8 = None
    if kernel:
        from deeplearning_mpi_tpu.ops.pallas.flash_decode import (
            flash_decode,
            kernel_decode_block,
            quantize_kv,
        )

        # The dispatcher's own fit (VMEM, then tiling), so the int8 arm
        # below times the block the fused arm resolves to.
        fitted = kernel_decode_block(1024, k_buf.shape, k_buf.dtype)
        if fitted is None:
            raise SystemExit(
                f"--kernel: max_len {max_len} not tileable by the decode "
                "kernel (needs a power-of-two-halved block dividing it); "
                "the arm would silently time the walk fallback"
            )
        fused = functools.partial(
            decode_attention, block=1024, dense_max=0, use_kernel=True
        )
        shipped_walk = functools.partial(
            decode_attention, block=2048, dense_max=0
        )
        # int8-KV arm: half the cache bytes — the batching-resistant term
        # of the serving roofline (PERF_ANALYSIS §10). Same FITTED block as
        # the fused arm (a hardcoded 1024 would silently truncate attention
        # for non-multiple max_len). Exactness vs the dequantized oracle is
        # pinned in tests; this times the HBM win.
        k8_buf, k8_scale = quantize_kv(k_buf)
        v8_buf, v8_scale = quantize_kv(v_buf)

        def fused_q8(q, k8, v8, i, _b=fitted, _ks=k8_scale, _vs=v8_scale):
            return flash_decode(
                q, k8, v8, i, block=_b, k_scale=_ks, v_scale=_vs
            )

    def make_loop(fn):
        # Device-looped timing: ONE dispatch runs `n` serialized executions
        # of fn inside a jitted fori_loop whose carry feeds each iteration's
        # q from the previous output (scaled by a *runtime* eps=0 scalar, so
        # XLA can neither fold the dependence away nor hoist fn out of the
        # loop). A host-side loop of per-call dispatches measures dispatch
        # cadence, not device time, for a ~50 us kernel — it produced
        # physically impossible numbers (windowed decode getting CHEAPER
        # with more fill). n is traced -> one executable for any trip count.
        @jax.jit
        def loop(n, eps, q, k, v, i):
            def body(_, carry):
                out = fn(carry, k, v, i).astype(carry.dtype)
                return carry + eps.astype(carry.dtype) * out

            return lax.fori_loop(0, n, body, q)

        return loop

    def clock(fn, *args) -> float:
        # Two trip counts; the difference cancels the fixed dispatch +
        # sync cost. The long loop must put DEVICE time well above host
        # jitter (negative diffs appeared at 100 trips x ~50 us), hence
        # 10*steps trips and a median over 3 estimates.
        loop = make_loop(fn)
        n0, n1 = 16, 16 + 10 * steps
        eps = jnp.float32(0.0)
        host_sync(loop(n0, eps, *args).ravel()[:1])  # compile
        estimates = []
        for _ in range(3):
            t0 = time.perf_counter()
            host_sync(loop(n0, eps, *args).ravel()[:1])
            t1 = time.perf_counter()
            host_sync(loop(n1, eps, *args).ravel()[:1])
            t2 = time.perf_counter()
            estimates.append(((t2 - t1) - (t1 - t0)) / (n1 - n0) * 1e6)
        return sorted(estimates)[1]  # us/execution

    rows = []
    for fill in fills:
        i = jnp.int32(fill - 1)
        us_dense = clock(dense, q, repeat_kv(k_buf, rep), repeat_kv(v_buf, rep), i)
        us_win = clock(windowed, q, k_buf, v_buf, i)
        rows.append({
            "fill": fill, "max_len": max_len, "kv_heads": kv_heads,
            "dense_us_per_token": round(us_dense, 1),
            "windowed_us_per_token": round(us_win, 1),
            "speedup": round(us_dense / us_win, 2),
        })
        if sliding is not None:
            us_slide = clock(sliding, q, k_buf, v_buf, i)
            rows[-1]["sliding_window"] = window
            rows[-1]["sliding_us_per_token"] = round(us_slide, 1)
        if fused is not None:
            us_kern = clock(fused, q, k_buf, v_buf, i)
            us_ship = clock(shipped_walk, q, k_buf, v_buf, i)
            rows[-1]["kernel_us_per_token"] = round(us_kern, 1)
            rows[-1]["walk2048_us_per_token"] = round(us_ship, 1)
            rows[-1]["kernel_vs_shipped_walk"] = round(us_ship / us_kern, 2)
            if window:
                us_kw = clock(
                    functools.partial(
                        decode_attention, block=1024, dense_max=0,
                        use_kernel=True, window=window,
                    ),
                    q, k_buf, v_buf, i,
                )
                rows[-1]["kernel_windowed_us_per_token"] = round(us_kw, 1)
            us_q8 = clock(fused_q8, q, k8_buf, v8_buf, i)
            rows[-1]["kernel_int8kv_us_per_token"] = round(us_q8, 1)
            rows[-1]["int8kv_vs_kernel"] = round(us_kern / us_q8, 2)
        print(json.dumps(rows[-1]))
    return rows


def bench_e2e(max_len: int, *, new_tokens: int = 256,
              quantize: str = "none", kv_heads: int = 0) -> dict:
    """generate() tok/s on a ~110M LM (BASELINE.md flagship shape), prompt
    filling half the context so the windowed walk sees a realistic mix.
    ``quantize='int8'`` converts the block kernels (weight-only,
    ``ops.quant``); ``kv_heads`` sizes a GQA cache — the two decode
    bandwidth levers, measurable separately or together."""
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.models.generate import generate_jit

    cfg = TransformerConfig(
        vocab_size=256, num_layers=12, num_heads=12, head_dim=64,
        d_model=768, d_ff=3072, num_kv_heads=kv_heads or None,
    )
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = TransformerLM(config=cfg, dtype=dt)
    new_tokens = min(new_tokens, max_len // 2)  # small --max_len smokes
    prompt_len = max_len - new_tokens
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    if quantize == "int8":
        import dataclasses

        from deeplearning_mpi_tpu.ops.quant import quantize_lm_params

        params = quantize_lm_params(params)
        model = dataclasses.replace(model, quantized=True)

    # Same jitted entry the CLI ships — timing eager generate() would fold
    # per-call retracing into the window and measure a path no caller uses.
    fn = generate_jit(model, max_new_tokens=new_tokens, temperature=0.0)
    rng = jax.random.key(0)

    # Median of 3 timed calls, distinct prompt content each, synced by
    # host_sync inside the timed region — a 2048-position decode once
    # "measured" 0.23 ms wall, ~40x faster than its own per-token attention
    # cost, because only dispatch was timed.
    from deeplearning_mpi_tpu.utils.profiling import host_sync

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
    dt_s = sorted(times)[len(times) // 2]
    positions = prompt_len + new_tokens  # the scan decodes every position
    row = {
        "e2e_context": max_len, "new_tokens": new_tokens,
        "quantize": quantize, "kv_heads": kv_heads or cfg.num_heads,
        "positions_decoded": positions,
        "seconds": round(dt_s, 3),
        "positions_per_s": round(positions / dt_s, 1),
    }
    print(json.dumps(row))
    return row


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--max_len", type=int, default=2048)
    parser.add_argument("--fills", type=int, nargs="+", default=None,
                        help="prefix lengths to time (default: max_len/8, /2, full)")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--heads", type=int, default=12)
    parser.add_argument("--num_kv_heads", type=int, default=0,
                        help="GQA: K/V buffer heads (0 = --heads); the "
                        "windowed path reads the grouped buffers natively")
    parser.add_argument("--head_dim", type=int, default=64)
    parser.add_argument("--window", type=int, default=0,
                        help="sliding-window size: adds a third arm timing "
                        "the O(window)-reads decode walk, which should be "
                        "FLAT in the fill")
    parser.add_argument("--kernel", action="store_true",
                        help="add a fourth arm timing the fused Pallas "
                        "decode kernel (ops/pallas/flash_decode.py) — the "
                        "on-chip measurement that decides the dispatcher's "
                        "auto-select")
    parser.add_argument("--e2e", action="store_true",
                        help="also run the ~110M-LM generate() end-to-end")
    parser.add_argument("--quantize", default="none", choices=("none", "int8"),
                        help="weight-only int8 kernels for the --e2e model")
    parser.add_argument("--spec", action="store_true",
                        help="also run the speculative + large-batch paged "
                        "engine vs the single-stream harness "
                        "(bench.bench_spec_decode) and emit the bench.py "
                        "combined-JSON line last")
    parser.add_argument("--spec_batch", type=int, default=32,
                        help="concurrent requests in the --spec engine arm")
    parser.add_argument("--spec_k", type=int, default=1,
                        help="draft proposals per sequence per verify step")
    parser.add_argument("--draft_layers", type=int, default=1,
                        help="self-draft depth (target layers reused)")
    parser.add_argument("--spec_context", type=int, default=128,
                        help="total positions per request in the --spec arms")
    parser.add_argument("--spec_new_tokens", type=int, default=96,
                        help="generated tokens per request in the --spec arms")
    parser.add_argument("--tuning_db", default=None, metavar="PATH",
                        help="tuning DB to consult (decode-bucket entries "
                        "land in the combined line's tuning_provenance)")
    parser.add_argument("--platform", default=None, choices=("cpu", "tpu"))
    args = parser.parse_args(argv)

    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform(args.platform)
    if args.tuning_db:
        from deeplearning_mpi_tpu.compiler import autotune

        autotune.set_default_db(args.tuning_db)

    fills = args.fills or [args.max_len // 8, args.max_len // 2, args.max_len]
    bench_attention(
        args.max_len, fills,
        batch=args.batch, heads=args.heads, head_dim=args.head_dim,
        kv_heads=args.num_kv_heads, window=args.window, kernel=args.kernel,
    )
    if args.e2e:
        bench_e2e(
            args.max_len, quantize=args.quantize, kv_heads=args.num_kv_heads
        )
    if args.spec:
        # bench.py owns the three-arm measurement (spec engine, plain
        # engine, single-stream baseline); this tool reuses it so the
        # micro-bench and the headline bench can never disagree on recipe.
        import bench

        detail = bench.bench_spec_decode(
            context=args.spec_context, new_tokens=args.spec_new_tokens,
            batch=args.spec_batch, spec_k=args.spec_k,
            draft_layers=args.draft_layers,
        )
        print(json.dumps({
            "metric": "lm_110m_spec_decode_positions_per_sec",
            "value": detail.get("positions_per_s"),
            "accepted_tokens_per_s": detail.get("accepted_tokens_per_s"),
            "acceptance_rate": detail.get("acceptance_rate"),
            "unit": "positions/s",
        }), flush=True)
        # LAST line: the exact combined schema bench.py's driver parses,
        # with this run's detail (and its consulted decode-bucket entries)
        # under details.lm_spec_decode / details.tuning_provenance.
        details = {"lm_spec_decode": detail}
        if detail.get("tuning_provenance"):
            details["tuning_provenance"] = detail["tuning_provenance"]
        print(bench._combined_line(details), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
