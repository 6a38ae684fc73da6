"""Continuous-batching LM serving benchmark — trace replay + latency report.

The offline ``dmt-generate`` answers "what does this checkpoint say"; this
CLI answers "how does it SERVE": it replays a request trace (Poisson
arrivals or a JSONL file) through the ``serving`` engine — paged KV cache,
chunked prefill interleaved with decode, admission control — and reports
the latency numbers serving is judged on: TTFT (arrival → first generated
token), TPOT (decode-phase seconds per token), and aggregate generated
tokens/s, plus the engine's live counters (queue depth, slot occupancy,
shed requests, KV blocks in use) through the telemetry registry
(``--metrics_file`` appends the canonical JSONL records
``tools/metrics_report.py`` reads; see docs/OBSERVABILITY.md).

Trace file format: one JSON object per line —
``{"arrival": seconds-from-start, "prompt": "text", "max_new": N,
"deadline": seconds-after-arrival (optional)}``; only ``prompt`` is
required (``arrival`` defaults to 0 — submit immediately).

``--selftest`` needs no checkpoint: it serves a tiny random-init model
against a synthetic Poisson trace and verifies every completion against
the offline greedy decode path token-for-token — the correctness contract
of continuous batching is that co-batched strangers never change your
output. ``make serve-smoke`` runs exactly this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import deque


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dmt-serve-lm",
        description="Replay a request trace through the continuous-batching "
        "serving engine; report TTFT/TPOT/tokens/s.",
    )
    from deeplearning_mpi_tpu.utils import config

    model = config.add_lm_model_flags(parser)
    model.title = (
        "model (MUST match the training run — the checkpoint stores arrays, "
        "not architecture)"
    )
    model.add_argument("--dtype", default="float32",
                       choices=("float32", "bfloat16"))
    parser.add_argument("--model_dir", default="saved_models")
    parser.add_argument("--model_filename", default="lm")
    parser.add_argument("--epoch", type=int, default=None)
    parser.add_argument("--ema", type=config.ema_decay, default=0.0,
                        help="nonzero = serve the EMA-averaged weights "
                        "(match the training run's --ema)")
    eng = parser.add_argument_group("engine")
    eng.add_argument("--max_slots", type=int, default=4,
                     help="concurrent decode slots (the jitted step's batch)")
    eng.add_argument("--block_size", type=int, default=16,
                     help="token positions per KV block")
    eng.add_argument("--num_blocks", type=int, default=64,
                     help="KV pool blocks per layer (one is scratch)")
    eng.add_argument("--max_blocks_per_seq", type=int, default=8,
                     help="block-table width; admission ceiling is "
                     "max_blocks_per_seq * block_size positions")
    eng.add_argument("--prefill_chunk", type=int, default=16,
                     help="prompt positions prefilled per slot per step "
                     "(chunked prefill interleaves with decode)")
    eng.add_argument("--max_queue", type=int, default=64,
                     help="bounded request queue; overflow is shed")
    eng.add_argument("--kv_dtype", default=None, choices=("int8",),
                     help="paged KV cache storage dtype (default: the "
                     "compute dtype). int8 stores per-(token,head) scales "
                     "and dequantizes in-gather — ~half the pool bytes per "
                     "position, so more resident sequences at fixed HBM; "
                     "lossy, so --selftest gates on token-level acceptance "
                     "vs the fp reference instead of bit-exact parity")
    eng.add_argument("--kv_acceptance_min", type=float, default=0.9,
                     help="minimum token-level acceptance rate vs offline "
                     "greedy the --selftest requires under a lossy "
                     "--kv_dtype (matched-prefix tokens / expected tokens)")
    eng.add_argument("--disagg", action="store_true",
                     help="disaggregated topology: a prefill-only engine "
                     "hands completed prompts (block tables over a shared "
                     "KV pool — no KV bytes move) to a decode-only engine, "
                     "so decode batches never stall behind long prefills; "
                     "with --replicas > 1 every replica runs disaggregated")
    eng.add_argument("--tuning_db", default=None,
                     help="autotuner tuning DB (tools/autotune.py output): "
                     "feeds --spec_k -1; the decode program does not "
                     "consult it")
    eng.add_argument("--warmup", action="store_true",
                     help="AOT-compile the decode and prefill programs "
                     "before accepting traffic (compiler/aot.py): first-"
                     "request latency contains zero compiles, and "
                     "compile-cache hit/miss counters land in the registry")
    eng.add_argument("--decode_buckets", default="",
                     help="comma-separated decode batch buckets, e.g. "
                     "'8,16,32': the scheduler briefly holds the decode "
                     "phase while enough supply exists to reach a larger "
                     "bucket, so verify/decode steps run at batched widths")
    eng.add_argument("--max_hold_steps", type=int, default=4,
                     help="max consecutive engine steps the scheduler may "
                     "hold decode while forming a larger batch bucket")
    eng.add_argument("--prefix_cache", action="store_true",
                     help="radix prefix cache: completed prompt prefixes "
                     "are indexed by token span and later requests adopt "
                     "the cached KV blocks (refcounted, copy-on-write) "
                     "instead of re-prefilling the shared span — streams "
                     "stay bit-identical to offline greedy")
    eng.add_argument("--tenants", default="",
                     help="per-tenant admission policy, e.g. "
                     "'prod=4096:1,batch=1024:0' — name=budget_tokens"
                     "[:priority]. budget_tokens bounds the tenant's "
                     "committed tokens (prompt + max_new over queued + "
                     "running; 0 = unlimited), over-budget submits are "
                     "shed with reason tenant_budget; higher priority "
                     "admits first. Trace entries pick their tenant via a "
                     "'tenant' field (default 'default')")
    spec = parser.add_argument_group(
        "speculative decoding (exact-greedy-match acceptance: output "
        "streams stay bit-identical to offline greedy regardless of "
        "draft quality)"
    )
    spec.add_argument("--spec_k", type=int, default=0,
                      help="draft tokens proposed per sequence per engine "
                      "step (0 = off; -1 = consult the tuning DB's "
                      "spec_k winner for this model/draft pair)")
    spec.add_argument("--draft_layers", type=int, default=0,
                      help="self-speculative draft: truncate the target to "
                      "its first N layers (tied embeddings reuse the "
                      "target's logit projection); required when spec_k "
                      "is nonzero")
    spec.add_argument("--draft_d_model", type=int, default=None,
                      help="custom draft width (random-init draft instead "
                      "of layer truncation; parity still holds — the "
                      "draft only proposes, the target decides)")
    spec.add_argument("--draft_d_ff", type=int, default=None)
    spec.add_argument("--draft_heads", type=int, default=None)
    spec.add_argument("--draft_head_dim", type=int, default=None)
    spec.add_argument("--draft_seed", type=int, default=0,
                      help="init seed for a custom-width draft")
    trace = parser.add_argument_group("trace")
    trace.add_argument("--trace", default=None,
                       help="JSONL request trace (see module docstring); "
                       "default: synthetic Poisson trace")
    trace.add_argument("--rate", type=float, default=20.0,
                       help="Poisson arrival rate, requests/s")
    trace.add_argument("--num_requests", type=int, default=16)
    trace.add_argument("--prompt_len_min", type=int, default=4)
    trace.add_argument("--prompt_len_max", type=int, default=24)
    trace.add_argument("--max_new_tokens", type=int, default=16,
                       help="generation budget per request (trace entries "
                       "may override)")
    trace.add_argument("--deadline", type=float, default=0.0,
                       help="seconds after arrival a QUEUED request is shed "
                       "(0 = no deadline; trace entries may override)")
    trace.add_argument("--eos_id", type=int, default=-1,
                       help="byte value that finishes a sequence (-1 = off)")
    trace.add_argument("--random_seed", type=int, default=0)
    fleet = parser.add_argument_group(
        "fleet (replicated serving: supervised replica processes behind "
        "the SLO-aware router — docs/SERVING.md)"
    )
    fleet.add_argument("--replicas", type=int, default=1,
                       help="serve through N supervised replica processes "
                       "(1 = single in-process engine); fleet mode implies "
                       "--selftest semantics (random-init model, parity "
                       "check against offline greedy)")
    fleet.add_argument("--autoscale", action="store_true",
                       help="closed-loop fleet sizing: spawn/retire "
                       "replicas from measured load (queue depth + backlog "
                       "per ready replica), with hysteresis + cooldown, a "
                       "hard --min_replicas floor, and the overload "
                       "brownout ladder (docs/SERVING.md); implies fleet "
                       "mode even with --replicas 1")
    fleet.add_argument("--autoscale_predictive", action="store_true",
                       help="predictive scale-up: forecast the load signal "
                       "(EWMA level + trend over the LoadSignal history) "
                       "and arm the up-window one --forecast_horizon_s "
                       "ahead, so replicas warm BEFORE a ramp lands "
                       "(docs/SIMULATION.md); implies --autoscale")
    fleet.add_argument("--forecast_horizon_s", type=float, default=3.0,
                       help="how far ahead the predictive forecaster "
                       "projects; should cover one spawn-to-ready warmup")
    fleet.add_argument("--forecast_tau_s", type=float, default=1.0,
                       help="EWMA time constant of the forecast load level")
    fleet.add_argument("--forecast_trend_tau_s", type=float, default=1.0,
                       help="EWMA time constant of the forecast load trend")
    fleet.add_argument("--min_replicas", type=int, default=1,
                       help="autoscaler floor: scale-down is vetoed at this "
                       "ready-replica count (a concurrent replica death "
                       "can never race the fleet to zero)")
    fleet.add_argument("--max_replicas", type=int, default=4,
                       help="autoscaler ceiling: scale-up is vetoed here; "
                       "sustained overload at the ceiling climbs the "
                       "brownout ladder instead")
    fleet.add_argument("--hedge_ms", type=float, default=0.0,
                       help="hedged-retry threshold: a request outstanding "
                       "this long (with deadline budget left) is duplicated "
                       "on a second replica; first completion wins, the "
                       "loser is cancelled (0 = hedging off)")
    fleet.add_argument("--swap_at", type=int, default=None,
                       help="after N completions, hot-swap every replica's "
                       "weights (rolling drain, zero downtime, zero "
                       "recompiles) to a fresh init from --random_seed + 1")
    fleet.add_argument("--fleet_dir", default=None,
                       help="scratch directory for replica mailboxes, "
                       "heartbeats, and logs (default: a fresh temp dir)")
    fleet.add_argument("--tp", type=int, default=1,
                       help="tensor-parallel degree per replica: each "
                       "replica's params are sharded across this many "
                       "devices (virtual CPU devices under JAX_PLATFORMS="
                       "cpu) via the Megatron column/row rules; requires "
                       "--replicas > 1")
    parser.add_argument("--metrics_file", default=None,
                        help="append canonical telemetry JSONL records here "
                        "(readable by tools/metrics_report.py)")
    parser.add_argument("--chaos", default=None,
                        help="deterministic fault-injection spec, e.g. "
                        "'serve_crash@step:12' — the engine crashes mid-step "
                        "and recovers (requeue + KV reconcile); with "
                        "--disagg also 'handoff_stall@step:N' (the "
                        "prefill→decode handoff wedges, then recovers); "
                        "with --replicas N > 1: 'replica_kill@step:4,"
                        "replica_hang@step:6' (fleet faults); falls back "
                        "to $DMT_CHAOS (docs/RESILIENCE.md)")
    parser.add_argument("--selftest", action="store_true",
                        help="random-init tiny-ish model, synthetic trace, "
                        "verify every completion against offline greedy "
                        "decode; exit 0 iff all match (no checkpoint needed)")
    run = parser.add_argument_group("runtime")
    run.add_argument("--platform", default=None, choices=("cpu", "tpu"))
    return parser


def _parse_tenants(spec: str):
    """``'prod=4096:1,batch=1024:0'`` -> the scheduler's tenants dict
    (``{name: {"budget_tokens": int, "priority": float}}``), or None for
    an empty spec."""
    spec = spec.strip()
    if not spec:
        return None
    tenants = {}
    for part in spec.split(","):
        part = part.strip()
        try:
            name, policy = part.split("=", 1)
            budget, _, priority = policy.partition(":")
            tenants[name.strip()] = {
                "budget_tokens": int(budget),
                "priority": float(priority) if priority else 0.0,
            }
        except ValueError:
            raise SystemExit(
                f"bad --tenants entry {part!r}: expected "
                "name=budget_tokens[:priority]"
            )
    return tenants


def _load_trace(path: str, default_max_new: int, default_deadline: float):
    import numpy as np

    entries = []
    try:
        lines = open(path, encoding="utf-8").read().splitlines()
    except OSError as e:
        raise SystemExit(f"cannot read --trace: {e}")
    for n, line in enumerate(lines, 1):
        if not line.strip():
            continue
        try:
            obj = json.loads(line)
            text = obj["prompt"]
        except (json.JSONDecodeError, KeyError, TypeError) as e:
            raise SystemExit(f"{path}:{n}: bad trace entry ({e})")
        prompt = np.frombuffer(
            text.encode("utf-8") or b"\x00", np.uint8
        ).astype(np.int32)
        entries.append({
            "arrival": float(obj.get("arrival", 0.0)),
            "prompt": prompt,
            "max_new": int(obj.get("max_new", default_max_new)),
            "deadline": float(obj.get("deadline", default_deadline)),
            "tenant": str(obj.get("tenant", "default")),
        })
    if not entries:
        raise SystemExit(f"{path}: empty trace")
    return sorted(entries, key=lambda e: e["arrival"])


def _poisson_trace(args):
    import numpy as np

    rng = np.random.default_rng(args.random_seed)
    t = 0.0
    entries = []
    for _ in range(args.num_requests):
        t += float(rng.exponential(1.0 / args.rate))
        n = int(rng.integers(args.prompt_len_min, args.prompt_len_max + 1))
        entries.append({
            "arrival": t,
            "prompt": rng.integers(1, 256, size=n).astype(np.int32),
            "max_new": args.max_new_tokens,
            "deadline": args.deadline,
        })
    return entries


def replay(engine, entries, *, poll_s: float = 0.0005):
    """Submit each entry at its arrival offset (wall clock) and step the
    engine until everything drains. Returns the Request records in
    submission order."""
    from deeplearning_mpi_tpu.resilience import InjectedFault

    # DisaggregatedEngine exposes idle() directly (two schedulers + a
    # handoff queue); the colocated engine's idleness is its scheduler's.
    idle = (
        engine.idle if hasattr(engine, "idle") else engine.scheduler.idle
    )
    pending = deque(entries)
    reqs = []
    t0 = time.monotonic()
    while pending or not idle():
        now = time.monotonic() - t0
        while pending and pending[0]["arrival"] <= now:
            e = pending.popleft()
            deadline = (
                t0 + e["arrival"] + e["deadline"] if e["deadline"] > 0
                else None
            )
            reqs.append(
                engine.submit(
                    e["prompt"], e["max_new"], deadline=deadline,
                    tenant=e.get("tenant", "default"),
                )
            )
        if not idle():
            try:
                engine.step()
            except InjectedFault as fault:
                print(f"chaos: {fault} — recovering", file=sys.stderr)
                engine.recover()
        elif pending:
            time.sleep(min(poll_s, max(pending[0]["arrival"] - now, 0.0)))
    return reqs, time.monotonic() - t0


def _report(reqs, wall_s, registry):
    from deeplearning_mpi_tpu.serving import RequestState

    # Looked up at call time: a default bound at import keeps whatever
    # stream stood in for stderr then (a test's capture, closed since).
    out = sys.stderr
    done = [r for r in reqs if r.state is RequestState.FINISHED]
    shed = [r for r in reqs if r.state is RequestState.SHED]
    tokens = sum(len(r.generated) for r in done)
    print(
        f"requests: {len(reqs)} submitted, {len(done)} completed, "
        f"{len(shed)} shed"
        + (
            " (" + ", ".join(
                f"{sum(1 for r in shed if r.shed_reason == why)} {why}"
                for why in sorted({r.shed_reason for r in shed})
            ) + ")"
            if shed else ""
        ),
        file=out,
    )
    snap = registry.snapshot()
    ttft = [k for k in ("serve_ttft_s_p50", "serve_ttft_s_p95") if k in snap]
    if done:
        print(
            f"completed tokens: {tokens} in {wall_s:.3f}s wall = "
            f"{tokens / wall_s:.1f} tokens/s",
            file=out,
        )
    if ttft:
        print(
            "TTFT p50/p95: "
            + "/".join(f"{snap[k] * 1e3:.1f}" for k in ttft) + " ms"
            + (
                f" | TPOT p50: {snap['serve_tpot_s_p50'] * 1e3:.2f} ms"
                if "serve_tpot_s_p50" in snap else ""
            ),
            file=out,
        )
    print(
        f"engine: {snap.get('serve_decode_steps', 0):.0f} decode steps, "
        f"{snap.get('serve_prefill_chunks', 0):.0f} prefill chunks"
        + (
            f", {snap['serve_decode_held_steps']:.0f} held for batching"
            if snap.get("serve_decode_held_steps") else ""
        ),
        file=out,
    )
    if "serve_prefix_hits_total" in snap:
        print(
            f"prefix cache: {snap['serve_prefix_hits_total']:.0f} hits, "
            f"{snap.get('serve_prefix_tokens_reused_total', 0):.0f} prefill "
            f"tokens reused, "
            f"{snap.get('serve_prefix_cow_copies_total', 0):.0f} CoW copies, "
            f"{snap.get('serve_prefix_evictions_total', 0):.0f} evictions",
            file=out,
        )
    if snap.get("serve_handoffs_total"):
        print(
            f"disagg: {snap['serve_handoffs_total']:.0f} prefill→decode "
            f"handoffs, {snap.get('serve_handoff_stalls_total', 0):.0f} "
            "stalled step(s)",
            file=out,
        )
    prop = snap.get("spec_proposed_total", 0)
    if prop:
        acc = snap.get("spec_accepted_total", 0)
        rb = snap.get("spec_rollback_total", 0)
        print(
            f"speculative: {prop:.0f} proposed, {acc:.0f} accepted "
            f"({acc / prop:.1%}), {rb:.0f} rolled back "
            f"({snap.get('spec_blocks_rolled_back_total', 0):.0f} KV "
            f"blocks) | accepted draft tokens/s: {acc / wall_s:.1f}",
            file=out,
        )


def _first_divergence_margins(cfg, model, params, diverged):
    """Judge each first divergence between the engine and offline greedy.

    The two are different programs (chunked paged prefill and a batched
    decode step; one whole-prompt prefill and a decode scan), so in a
    reduced-precision dtype they round differently and may pick different
    argmaxes where the top logits nearly tie. For every ``(request, agreed
    prefix length, engine token, offline token)`` the agreed sequence is run
    through the model twice — in float32 at the highest matmul precision
    (the reference) and in the compute dtype — giving

    - ``margin``: the reference's top logit minus the reference logit of
      the worse of the two tokens, and
    - ``eps``: the largest difference between compute-dtype and reference
      logits over the vocabulary at that position, i.e. the rounding error
      one such program carries there.

    Two programs each within ``eps`` of the reference can disagree only
    where ``margin <= 2 * eps``: that is a rounding tie. Anything larger is
    a real divergence. In float32 on CPU ``eps`` is 0, so the rule is exact
    bit-identity there. Yields ``(request, token index, margin, eps)``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerLM

    ref_model = TransformerLM(config=cfg, dtype=jnp.float32)
    # One padded length for every request: one compile per dtype. The model
    # is causal, so right-padding cannot reach the position that is read.
    pad_to = max(r.prompt_len + r.max_new_tokens for r, *_ in diverged)

    @jax.jit
    def ref_logits(params, tokens):
        with jax.default_matmul_precision("float32"):
            return ref_model.apply({"params": params}, tokens)

    @jax.jit
    def compute_logits(params, tokens):
        return model.apply({"params": params}, tokens)

    for r, agree, engine_tok, offline_tok in diverged:
        known = np.concatenate([r.prompt, r.generated[:agree]]).astype(np.int32)
        tokens = jnp.zeros((1, pad_to), jnp.int32).at[0, : len(known)].set(known)
        hi = np.asarray(ref_logits(params, tokens)[0, len(known) - 1], np.float32)
        lo = np.asarray(
            compute_logits(params, tokens)[0, len(known) - 1], np.float32
        )
        margin = float(hi.max() - min(hi[engine_tok], hi[offline_tok]))
        yield r, agree, margin, float(np.abs(lo - hi).max())


def _run_fleet(args, eos_id) -> int:
    """--replicas N > 1: route the trace through a supervised replica
    fleet instead of one in-process engine, then hold every completion to
    the same offline-greedy parity bar as --selftest — including requests
    that failed over between replicas mid-flight."""
    import tempfile

    from deeplearning_mpi_tpu.serving import FleetFailure, FleetSupervisor
    from deeplearning_mpi_tpu.telemetry import JsonlSink, MetricsRegistry

    if args.spec_k:
        print("--replicas > 1 does not compose with --spec_k yet",
              file=sys.stderr)
        return 1
    model_spec = {
        "vocab_size": 256,
        "num_layers": args.num_layers,
        "num_heads": args.num_heads,
        "num_kv_heads": args.num_kv_heads or None,
        "head_dim": args.head_dim,
        "d_model": args.d_model,
        "d_ff": args.d_ff,
        "attention_window": args.attention_window,
    }
    engine_spec = {
        "max_slots": args.max_slots,
        "block_size": args.block_size,
        "num_blocks": args.num_blocks,
        "max_blocks_per_seq": args.max_blocks_per_seq,
        "prefill_chunk": args.prefill_chunk,
        "max_queue": args.max_queue,
        "prefix_cache": args.prefix_cache,
    }
    if args.trace:
        entries = _load_trace(args.trace, args.max_new_tokens, args.deadline)
    else:
        entries = _poisson_trace(args)
    fleet_dir = args.fleet_dir or tempfile.mkdtemp(prefix="dmt_fleet_")
    registry = MetricsRegistry()
    if args.metrics_file:
        registry.add_sink(JsonlSink(args.metrics_file))
    autoscale = None
    if args.autoscale:
        from deeplearning_mpi_tpu.serving import AutoscalerConfig

        autoscale = AutoscalerConfig(
            min_replicas=args.min_replicas, max_replicas=args.max_replicas,
            predictive=args.autoscale_predictive,
            forecast_horizon_s=args.forecast_horizon_s,
            forecast_tau_s=args.forecast_tau_s,
            forecast_trend_tau_s=args.forecast_trend_tau_s,
        )
    sup = FleetSupervisor(
        model_spec, engine_spec, args.replicas, fleet_dir,
        seed=args.random_seed, eos_id=eos_id, warmup=True,
        chaos=args.chaos, hedge_ms=args.hedge_ms, registry=registry,
        disagg=args.disagg, tp=args.tp, tenants=_parse_tenants(args.tenants),
        autoscale=autoscale,
    )
    swap_seed = args.random_seed + 1 if args.swap_at is not None else None
    try:
        result = sup.run(entries, swap_at=args.swap_at, swap_seed=swap_seed)
    except FleetFailure as e:
        print(f"fleet FAILED: {e} (logs under {fleet_dir})", file=sys.stderr)
        return 1
    shed = ", ".join(f"{n} {why}" for why, n in sorted(result.shed.items()))
    print(
        f"fleet: {result.completed} completed, "
        f"{sum(result.shed.values())} shed" + (f" ({shed})" if shed else "")
        + f", {result.dropped} dropped | {result.redispatched} re-dispatched "
        f"across {result.restarts} restart(s)",
        file=sys.stderr,
    )
    snap = result.snapshot
    if snap.get("serve_hedge_total", 0):
        parts = []
        for k in sorted(snap):
            if k.startswith("serve_hedge_total{"):
                outcome = k.split("=", 1)[1].strip('"}')
                parts.append(f"{snap[k]:.0f} {outcome}")
        print("hedges: " + ", ".join(parts), file=sys.stderr)
    if result.scale:
        print(
            f"autoscale: {result.scale['spawned']} spawned, "
            f"{result.scale['retired']} retired, "
            f"{result.scale['vetoed']} vetoed "
            f"({result.scale['events']} decisions), brownout max stage "
            f"{result.scale['brownout_stage_max']}, final fleet "
            f"{result.scale['replicas_final']}",
            file=sys.stderr,
        )
    if result.swap["requested"]:
        print(
            f"swap: performed={result.swap['performed']} "
            f"drain={result.swap['drain_s'] and round(result.swap['drain_s'], 2)}s "
            f"completions_during={result.swap['completions_during']} "
            f"compile_flat={result.swap['compile_flat']}",
            file=sys.stderr,
        )
    registry.close()

    # Fleet parity: rebuild each weight version from (config, seed) and
    # hold every winning stream to offline greedy — the failover and
    # hedging machinery must be invisible in the tokens.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.models.generate import generate

    model = TransformerLM(
        config=TransformerConfig(**model_spec), dtype=jnp.float32
    )
    params_by_version = {}

    def version_params(version):
        if version not in params_by_version:
            seed = args.random_seed if version == 0 else swap_seed
            params_by_version[version] = model.init(
                jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        return params_by_version[version]

    mismatched = 0
    for rid, rec in sorted(result.requests.items()):
        out = generate(
            model, version_params(rec["version"]),
            jnp.asarray(rec["prompt"], jnp.int32)[None],
            max_new_tokens=rec["max_new"], rng=jax.random.key(0),
            temperature=0.0, eos_id=eos_id,
        )
        expect = np.asarray(out)[0, len(rec["prompt"]):].tolist()
        if eos_id is not None and eos_id in expect:
            expect = expect[: expect.index(eos_id) + 1]
        if rec["tokens"] != expect:
            mismatched += 1
            print(
                f"fleet parity: rid {rid} (version {rec['version']}) "
                f"diverged from offline greedy:\n"
                f"  fleet  : {rec['tokens']}\n  offline: {expect}",
                file=sys.stderr,
            )
    if mismatched or not result.ok:
        print(
            f"fleet FAILED: ok={result.ok} (dropped={result.dropped}, "
            f"compile_flat={result.compile_flat}, "
            f"chaos_balanced={result.chaos_balanced}), "
            f"{mismatched} parity mismatch(es); logs under {fleet_dir}",
            file=sys.stderr,
        )
        return 1
    peak = args.replicas
    if result.scale:
        peak = max(peak, args.replicas + result.scale["spawned"])
    print(
        f"fleet OK: {result.completed} requests bit-identical to offline "
        f"greedy across {peak} replica(s)",
        file=sys.stderr,
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.autoscale_predictive:
        args.autoscale = True  # predictive is a mode OF the autoscaler
    eos_id = args.eos_id if args.eos_id >= 0 else None
    if eos_id is not None and eos_id > 255:
        print(f"--eos_id {eos_id} is outside the byte vocab (0-255)",
              file=sys.stderr)
        return 1
    # Fail loud on chaos kinds this workload has no injection hook for:
    # a kind that can never fire would silently pass every drill while
    # keeping the reconciliation invariant unfalsifiable. CONTROLPLANE_KINDS
    # (supervisor_kill/supervisor_hang) are deliberately absent from every
    # set below: this CLI process IS the supervisor and nothing restarts
    # it, so planning its own death could never close the books. Only
    # harnesses with a restart loop around the supervisor may plan them
    # (tools/controlplane_drill.py).
    import os as _os

    chaos_spec = args.chaos or _os.environ.get("DMT_CHAOS") or ""
    if chaos_spec.strip():
        from deeplearning_mpi_tpu.resilience import (
            AUTOSCALE_KINDS,
            DISAGG_KINDS,
            FLEET_KINDS,
            SERVE_KINDS,
            validate_plan_kinds,
        )

        if args.autoscale:
            supported = FLEET_KINDS | AUTOSCALE_KINDS
            workload = "autoscaled serving fleet"
        elif args.replicas > 1:
            supported, workload = FLEET_KINDS, "serving fleet"
        elif args.disagg:
            supported, workload = DISAGG_KINDS, "disaggregated serving"
        else:
            supported, workload = SERVE_KINDS, "single-replica serving"
        try:
            validate_plan_kinds(chaos_spec, supported, workload=workload)
        except ValueError as e:
            print(f"--chaos: {e}", file=sys.stderr)
            return 1
    from deeplearning_mpi_tpu.runtime import bootstrap

    if args.replicas > 1 or args.autoscale:
        if args.kv_dtype:
            # Fleet parity is a bit-exact bar (failover must be invisible
            # in the tokens); a lossy KV cache would make it vacuous.
            print("--kv_dtype does not compose with fleet mode: fleet "
                  "parity is bit-exact", file=sys.stderr)
            return 1
        bootstrap.select_platform(args.platform)
        return _run_fleet(args, eos_id)
    if args.tp > 1:
        print("--tp > 1 shards replica processes; it requires "
              "--replicas > 1", file=sys.stderr)
        return 1
    if args.moe_experts > 0:
        # Same fail-fast rule as dmt-generate's composition checks: the
        # engine would raise anyway, but before minutes of init/restore.
        print(
            "serving is dense-MLP only: MoE capacity routing makes a "
            "token's output depend on co-batched strangers, breaking the "
            "engine's request-independence contract",
            file=sys.stderr,
        )
        return 1
    bootstrap.select_platform(args.platform)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.serving import (
        DisaggregatedEngine,
        EngineConfig,
        RequestState,
        ServingEngine,
    )
    from deeplearning_mpi_tpu.telemetry import JsonlSink, MetricsRegistry

    cfg = TransformerConfig(
        vocab_size=256,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        num_kv_heads=args.num_kv_heads or None,
        head_dim=args.head_dim,
        d_model=args.d_model,
        d_ff=args.d_ff,
        attention_window=args.attention_window,
    )
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = TransformerLM(config=cfg, dtype=dtype)

    if args.selftest:
        params = model.init(
            jax.random.key(args.random_seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    else:
        from pathlib import Path

        import optax

        from deeplearning_mpi_tpu.train import (
            Checkpointer,
            create_train_state,
        )
        from deeplearning_mpi_tpu.utils import config as uconfig

        ckpt_dir = Path(args.model_dir) / args.model_filename
        if not ckpt_dir.is_dir():
            print(f"no checkpoint found under {ckpt_dir} "
                  "(--selftest serves a random-init model)", file=sys.stderr)
            return 1
        err = uconfig.arch_mismatch_error(cfg, ckpt_dir)
        if err:
            print(err, file=sys.stderr)
            return 1
        template = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            optax.identity(), ema=args.ema > 0,
        )
        ckpt = Checkpointer(ckpt_dir)
        try:
            state = ckpt.restore_params_only(template, epoch=args.epoch)
        except Exception as e:  # noqa: BLE001 — orbax raises its own types;
            # one clean line beats a multi-frame traceback for a CLI.
            print(f"failed to restore from {ckpt.directory}: {e}",
                  file=sys.stderr)
            return 1
        finally:
            ckpt.close()
        params = state.params if state.ema_params is None else state.ema_params

    registry = MetricsRegistry()
    if args.metrics_file:
        registry.add_sink(JsonlSink(args.metrics_file))
    from deeplearning_mpi_tpu.resilience import ChaosInjector

    chaos = ChaosInjector.from_spec(args.chaos, registry=registry)
    if args.tuning_db:
        from deeplearning_mpi_tpu.compiler.autotune import set_default_db

        set_default_db(args.tuning_db)

    spec_k = args.spec_k
    if spec_k and args.draft_layers < 1:
        print("--spec_k needs a draft model: pass --draft_layers N "
              "(self-speculative layer truncation)", file=sys.stderr)
        return 1
    if spec_k == -1:
        from deeplearning_mpi_tpu.compiler import autotune

        tuned = autotune.tuned_spec_k(cfg, args.draft_layers, dtype)
        spec_k = tuned["spec_k"] if tuned else 0
        print(
            f"spec_k from tuning DB: {spec_k}"
            + (f" (tuned accept_rate {tuned['accept_rate']:.2f})" if tuned
               else " (no spec_k entry for this model/draft — disabled)"),
            file=sys.stderr,
        )
    draft_cfg = draft_params = None
    if spec_k > 0:
        from deeplearning_mpi_tpu.models import draft_config, truncate_lm_params

        overrides = {
            k: v for k, v in (
                ("d_model", args.draft_d_model),
                ("d_ff", args.draft_d_ff),
                ("num_heads", args.draft_heads),
                ("head_dim", args.draft_head_dim),
            ) if v is not None
        }
        draft_cfg = draft_config(cfg, args.draft_layers, **overrides)
        if overrides:
            # Width changed: target arrays can't be reused. Random init —
            # acceptance will be poor until the draft is trained, but the
            # exact-match rule keeps outputs correct regardless.
            draft_model = TransformerLM(config=draft_cfg, dtype=dtype)
            draft_params = draft_model.init(
                jax.random.key(args.draft_seed), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        else:
            draft_params = truncate_lm_params(params, args.draft_layers)

    try:
        decode_buckets = tuple(
            int(b) for b in args.decode_buckets.split(",") if b.strip()
        )
    except ValueError:
        print(f"bad --decode_buckets {args.decode_buckets!r}: expected "
              "comma-separated integers like '8,16,32'", file=sys.stderr)
        return 1
    engine_cls = DisaggregatedEngine if args.disagg else ServingEngine
    engine = engine_cls(
        cfg, params,
        EngineConfig(
            max_slots=args.max_slots,
            block_size=args.block_size,
            num_blocks=args.num_blocks,
            max_blocks_per_seq=args.max_blocks_per_seq,
            prefill_chunk=args.prefill_chunk,
            max_queue=args.max_queue,
            spec_k=spec_k,
            decode_buckets=decode_buckets,
            max_hold_steps=args.max_hold_steps,
            kv_dtype=args.kv_dtype,
            prefix_cache=args.prefix_cache,
        ),
        dtype=dtype, eos_id=eos_id, registry=registry, chaos=chaos,
        draft_config=draft_cfg, draft_params=draft_params,
        tenants=_parse_tenants(args.tenants),
    )
    if args.warmup:
        t_warm = time.monotonic()
        engine.warmup()
        print(f"warmup: decode+prefill compiled in "
              f"{time.monotonic() - t_warm:.2f}s", file=sys.stderr)
        # The compile counters as warm-up left them: a reader diffs
        # serve_summary against this to see that traffic compiled nothing.
        registry.emit("serve_warmup", registry.snapshot())

    if args.trace:
        entries = _load_trace(args.trace, args.max_new_tokens, args.deadline)
    else:
        entries = _poisson_trace(args)
    too_long = [
        i for i, e in enumerate(entries)
        if len(e["prompt"]) + e["max_new"] > engine.engine.max_seq_len
    ]
    if too_long:
        print(
            f"warning: {len(too_long)} request(s) exceed the engine's "
            f"{engine.engine.max_seq_len}-position ceiling "
            "(max_blocks_per_seq * block_size) and will be shed at submit",
            file=sys.stderr,
        )

    reqs, wall_s = replay(engine, entries)
    _report(reqs, wall_s, registry)
    if chaos is not None:
        print(chaos.summary(), file=sys.stderr)
    registry.emit("serve_summary", registry.snapshot())
    registry.close()

    if not args.selftest:
        for r in reqs:
            if r.state is RequestState.FINISHED:
                text = np.asarray(r.generated, np.uint8).tobytes().decode(
                    "utf-8", errors="replace"
                )
                print(f"[{r.rid}] {text!r}")
        return 0

    # Selftest parity: every completed request must match the offline
    # greedy decode of the same prompt token-for-token — a completion that
    # depends on which strangers shared the batch is the one bug class a
    # continuous-batching engine must never have.
    from deeplearning_mpi_tpu.models.generate import generate

    done = [r for r in reqs if r.state is RequestState.FINISHED]
    if len(done) != len(reqs):
        bad = [(r.rid, r.state.value, r.shed_reason) for r in reqs
               if r.state is not RequestState.FINISHED]
        print(f"selftest: not all requests completed: {bad}", file=sys.stderr)
        return 1
    kv_lossy = args.kv_dtype is not None
    mismatched = 0
    diverged = []  # (request, agreed prefix length, engine tok, offline tok)
    tokens_expected = 0
    tokens_accepted = 0
    for r in done:
        out = generate(
            model, params, jnp.asarray(r.prompt)[None],
            max_new_tokens=r.max_new_tokens, rng=jax.random.key(0),
            temperature=0.0, eos_id=eos_id,
        )
        expect = np.asarray(out)[0, r.prompt_len :].tolist()
        if eos_id is not None and eos_id in expect:
            # offline pads with EOS to the static window; the engine stops.
            expect = expect[: expect.index(eos_id) + 1]
        # Matched-prefix length: greedy decode forks permanently at the
        # first divergent token, so the prefix is the honest agreement
        # measure for the lossy-KV acceptance gate.
        agree = 0
        for a, b in zip(r.generated, expect):
            if a != b:
                break
            agree += 1
        tokens_expected += len(expect)
        tokens_accepted += agree
        if r.generated != expect:
            mismatched += 1
            if agree < min(len(r.generated), len(expect)):
                diverged.append((r, agree, r.generated[agree], expect[agree]))
    if kv_lossy:
        # A quantized KV cache is allowed to perturb tokens — but only so
        # far. The gate is MEASURED acceptance against the fp reference,
        # not a promise: quantization bugs (wrong scale, stale epoch)
        # crater acceptance and fail here.
        acceptance = tokens_accepted / max(tokens_expected, 1)
        if acceptance < args.kv_acceptance_min:
            print(
                f"selftest FAILED: {args.kv_dtype} KV acceptance "
                f"{acceptance:.1%} ({tokens_accepted}/{tokens_expected} "
                f"tokens match the fp reference) below the "
                f"--kv_acceptance_min {args.kv_acceptance_min:.1%} gate",
                file=sys.stderr,
            )
            return 1
        print(
            f"selftest {args.kv_dtype} KV: acceptance {acceptance:.1%} "
            f"({tokens_accepted}/{tokens_expected} tokens, "
            f"{mismatched} stream(s) diverged) >= "
            f"{args.kv_acceptance_min:.1%} gate",
            file=sys.stderr,
        )
    elif mismatched:
        real = mismatched - len(diverged)  # a length mismatch is never a tie
        worst = 0.0
        for r, at, margin, eps in _first_divergence_margins(
            cfg, model, params, diverged
        ):
            tie = margin <= 2 * eps
            real += not tie
            worst = max(worst, margin)
            print(
                f"selftest: rid {r.rid} first diverges at token {at} — "
                f"float32 logit margin between the two candidates and the top "
                f"{margin:.3g}, {args.dtype} rounding 2*eps {2 * eps:.3g}: "
                + ("rounding tie" if tie else "REAL divergence"),
                file=sys.stderr,
            )
        if real:
            print(f"selftest FAILED: {real}/{len(done)} request(s) diverged "
                  "beyond a rounding tie", file=sys.stderr)
            return 1
        print(
            f"selftest: {len(done) - mismatched}/{len(done)} requests "
            f"bit-identical; {mismatched} first diverge at a rounding tie "
            f"(largest float32 logit margin {worst:.3g}, each within twice "
            f"the {args.dtype} forward's own rounding error there)",
            file=sys.stderr,
        )
    if spec_k > 0:
        snap = registry.snapshot()
        prop = snap.get("spec_proposed_total", 0)
        acc = snap.get("spec_accepted_total", 0)
        rb = snap.get("spec_rollback_total", 0)
        if prop != acc + rb:
            print(f"selftest FAILED: speculative counters do not "
                  f"reconcile: proposed {prop:.0f} != accepted {acc:.0f} "
                  f"+ rolled back {rb:.0f}", file=sys.stderr)
            return 1
        if not prop or not acc:
            print(f"selftest FAILED: speculative path inert (proposed "
                  f"{prop:.0f}, accepted {acc:.0f}) — the draft should "
                  "land at least some exact matches", file=sys.stderr)
            return 1
        print(f"selftest speculative: {prop:.0f} proposed = {acc:.0f} "
              f"accepted + {rb:.0f} rolled back (rate {acc / prop:.1%})",
              file=sys.stderr)
    if kv_lossy:
        bar = f"within the {args.kv_acceptance_min:.1%} acceptance gate vs"
    elif mismatched:
        bar = "bit-identical (up to rounding ties) to"
    else:
        bar = "bit-identical to"
    print(
        f"selftest OK: {len(done)} requests {bar} offline "
        f"greedy decode ({engine.pool.total_allocated} block allocations, "
        f"{engine.pool.total_freed} frees)",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
