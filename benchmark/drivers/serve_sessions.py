"""Served cells of closed, replayed sessions: a fixed list of long prompts, all
submitted when set-up ends, decoded greedy from the lead to the close.

The traffic file lists the prompts' lengths. The lead lasts until every
session has its first token and ``lead_after_first_tokens_s`` more; then the
window of ``--seconds`` opens: every step of it is the same decode program on
the same rows, each context one token longer than the step before. At the
close every session is cancelled (no drain). Gaps between tokens are taken as
``stats.serve_metrics`` takes them, from when ``step`` returned a token to the
caller. ``--seed`` makes the weights and the token ids; no two prompts share a
prefix. A traffic file may carry ``engine`` keys that override the
configuration's group. A configuration names the package that knows it
(``program``, ``weights``, ``reference``) under ``"modules"``; without the key
they are the benchmark's first ones.

What decides ``correct`` beside the exact counts: the gaps by which the last
``JUDGED`` tokens two sessions served inside the window lie below the
reference's best (``served_logit_gap``: the widest; ``_p50``, ``_mean``: over
all of them). Where the traffic file has ``probe_after_close``, one short
request is served by the same engine once the sessions are cancelled, its
context shorter than the model's top-k from first token to last: there a
selecting model keeps every key, so no rounding can swap a kept key and the
gap (``probe_logit_gap`` and its ``_p50``, ``_mean``) reads what the expert
layer, the projections and the selection's own count do. And where the cell
limits ``index_key_gap``: the indexer keys the pool holds for the judged
sessions' positions at the first layer, which depend on the token and its
position alone, against the reference's (the widest relative error of one
position's key). A cell's file limits the numbers that separate there; the
others are printed as readings.
"""

from __future__ import annotations

import gc
import importlib
import time
from typing import Any

import numpy as np

from benchmark import schedule, stats, trace
from benchmark.drivers.serve import step_work
from benchmark.run import Run, log, memory_peak

COUNTERS = (
    "serve_decode_steps", "serve_select_live_keys", "serve_select_kept_keys",
    "serve_moe_experts_touched", "serve_moe_expert_slots",
)
JUDGED = 256  # served tokens compared a session, the last inside the window
STATS = {"": "widest", "_p50": "p50", "_mean": "mean"}  # a limit's suffix -> the statistic of the gaps it holds


def modules(cfg: dict[str, Any]) -> tuple[Any, Any, Any]:
    """``(program, weights, reference)`` of a configuration."""
    package = "benchmark." + cfg["modules"] if "modules" in cfg else "benchmark"
    return tuple(importlib.import_module(f"{package}.{name}") for name in ("program", "weights", "reference"))


def engine_settings(run: Run) -> dict[str, Any]:
    merged = {**run.config["engine"], **run.traffic.get("engine", {})}
    return {k: v for k, v in merged.items() if k != "why"}


def build_engine(run: Run) -> tuple[Any, Any]:
    """One engine with the seed's weights, warmed up, and its registry."""
    import jax

    from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine
    from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

    program, weights, _ = modules(run.config)
    cfg, dtype = run.config, program.compute_dtype(run.config)
    model = program.model_config(cfg)  # first: a program that lacks the model's properties fails here, at once
    engine_cfg = EngineConfig(**engine_settings(run))
    registry = MetricsRegistry()
    run.setup.phase("program_imports")
    params = jax.jit(lambda words: weights.build(cfg, words, dtype))(weights.seed_words(run.seed))
    jax.block_until_ready(params)
    engine = ServingEngine(model, params, engine_cfg, dtype=dtype, clock=time.monotonic, registry=registry)
    del params
    run.setup.phase("weights_and_engine")
    engine.warmup()
    run.setup.phase("warmup")
    run.spans["compile"] = [run.setup.phases["warmup"]]
    # one request through submit and step before any is timed: two prefill chunks, the first
    # token's argmax and decode steps, so that the small programs around the engine's are compiled too
    engine.submit(np.arange(engine_cfg.prefill_chunk + 3, dtype=np.int32) % cfg["vocab_size"], 3)
    engine.run_until_idle()
    run.setup.phase("warm_request")
    return engine, registry


def counters(registry: Any) -> dict[str, float]:
    snap = registry.snapshot()
    return {name: float(snap.get(name, 0.0)) for name in COUNTERS}


def serve(run: Run, engine: Any, registry: Any, prompts: list[np.ndarray]) -> dict[str, Any]:
    """The lead and the window. Returns the client's record of every session
    and the clock readings around them."""
    import jax

    traffic, seconds = run.traffic, run.seconds
    room = engine.engine.max_seq_len
    t0 = time.monotonic()
    records = [{"due": 0.0, "tokens": [], "req": engine.submit(p, room - len(p))} for p in prompts]
    lead_end = close = trace_at = float("inf")
    tracing, before = False, None
    step_s: list[float] = []
    while True:
        now = time.monotonic() - t0
        if now >= close:
            break
        if lead_end == float("inf") and all(rec["tokens"] for rec in records):
            lead_end = now + traffic["lead_after_first_tokens_s"]
            close = lead_end + seconds
            trace_at = max(lead_end, close - traffic["trace_seconds"]) if run.trace else float("inf")
        if not tracing and trace_at <= now:
            # the slice traced is the END of the window; stopping the profiler blocks for most of
            # a second per second traced, so it is stopped once the window has closed
            trace.start(run.trace_dir)
            tracing, before = True, counters(registry)
        if any(rec["req"].state.value in ("finished", "shed") for rec in records) or now > traffic["lead_limit_seconds"] + seconds:
            break  # a session ended early, or the lead never did: the checks say so
        work = step_work(engine) if tracing else None
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            engine.step()
        seen = time.monotonic() - t0
        if lead_end <= now:
            step_s.append(seen - now)
        if work is not None:
            run.work.append(work)
        for rec in records:
            new = len(rec["req"].generated) - len(rec["tokens"])
            if new:
                rec["tokens"].extend([seen] * new)
    after, compiles = counters(registry), run.compiles.count
    if tracing:
        jax.profiler.stop_trace()
        log(f"profiler stopped in {time.monotonic() - t0 - close:.2f} s, after the window had closed")
    ended = sum(rec["req"].state.value in ("finished", "shed") for rec in records)
    picked = judged_sessions(run.seed, prompts)
    pooled = {}
    if "index_key_gap" in run.limits:  # read before the sessions are cancelled and their blocks go back
        pooled = {i: pooled_index_keys(engine, records[i]["req"]) for i in picked if records[i]["req"].blocks}
    for rec in records:
        engine.cancel(rec["req"])
    probe = None
    if "probe_after_close" in traffic and lead_end < float("inf"):
        p = traffic["probe_after_close"]
        ids = schedule.prompt_ids(run.seed, len(prompts), p["prompt_tokens"], run.config["vocab_size"])
        req = engine.submit(ids, p["new_tokens"])
        engine.run_until_idle()
        probe = (ids, list(req.generated))
    before = before or dict.fromkeys(COUNTERS, 0.0)
    return {
        "records": records, "lead_end": lead_end, "close": close, "step_s": step_s, "ended": ended, "compiles": compiles,
        "counters": {name: after[name] - before[name] for name in COUNTERS},
        "picked": picked, "pooled": pooled, "probe": probe,
    }


def judged_sessions(seed: int, prompts: list[np.ndarray]) -> list[int]:
    """The longest session and one drawn from the seed."""
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    rest = [i for i in range(len(prompts)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 13]))
    return [longest, int(rng.choice(rest))] if rest else [longest]


def pooled_index_keys(engine: Any, req: Any) -> np.ndarray:
    """The first layer's indexer keys the engine's pool holds for ``req``'s
    positions, ``[length, Di]`` float32, read through its block table."""
    pool = engine._kv[2]
    keys = np.asarray(pool[0, np.asarray(req.blocks, np.int32)].astype("float32"))
    return keys.reshape(-1, keys.shape[-1])[: req.length]


def judged(prompts: list[np.ndarray], out: dict[str, Any]) -> list[tuple[np.ndarray, list[int]]]:
    """(everything before, the last ``JUDGED`` tokens served inside the
    window) for each of the judged sessions."""
    pairs = []
    for i in out["picked"]:
        rec = out["records"][i]
        inside = sum(t < out["close"] for t in rec["tokens"])
        first = max(inside - JUDGED, sum(t < out["lead_end"] for t in rec["tokens"]))
        tokens = list(rec["req"].generated)
        if inside > first:
            pairs.append((np.concatenate([prompts[i], np.asarray(tokens[:first], np.int32)]), tokens[first:inside]))
    return pairs


def gaps(reference_logits: np.ndarray, tokens: Any) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's best."""
    logits = np.asarray(reference_logits, np.float64)
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), np.asarray(tokens)]


def seed_params(run: Run, seed: int) -> Any:
    """The seed's weights as the program holds them, for the reference."""
    import jax

    program, weights, _ = modules(run.config)
    cfg = run.config
    return jax.jit(lambda words: weights.build(cfg, words, program.compute_dtype(cfg)))(weights.seed_words(seed))


def reference_logits(run: Run, params: Any, served: list[tuple[np.ndarray, list[int]]], **how: Any) -> list[np.ndarray]:
    """The reference's logits at every judged position of each (ids before,
    served tokens) pair. ``how`` goes to the reference (``lower`` for the
    control, ``faults``)."""
    reference = modules(run.config)[2]
    out = []
    for before, tokens in served:
        ids = np.concatenate([before, np.asarray(tokens[:-1], np.int32)])
        rows = np.arange(len(before) - 1, len(ids))
        out.append(np.asarray(reference.serve_logits(run.config, params, ids, rows, **how)))
    return out


def gap_stats(logits: list[np.ndarray], tokens: list[Any], what: str) -> dict[str, float]:
    """The gaps by which ``tokens`` lie below the best of ``logits``, pair by
    pair: the widest (what ``check.served_gap`` reads), the median, the mean,
    and how many were compared."""
    if not logits:
        return {"widest": float("nan"), "p50": float("nan"), "mean": float("nan"), "compared": 0}
    g = np.concatenate([gaps(lg, t) for lg, t in zip(logits, tokens)])
    log(f"{what}: gaps of {len(g)} tokens below the reference's best: mean {g.mean():.4f}, p50 {np.quantile(g, 0.5):.4f}, p90 {np.quantile(g, 0.9):.4f}, "
        f"p99 {np.quantile(g, 0.99):.4f}, widest {g.max():.4f}; over 0.25: {100 * (g > 0.25).mean():.1f}%, over 1: {100 * (g > 1).mean():.1f}%")
    return {"widest": float(g.max()), "p50": float(np.quantile(g, 0.5)), "mean": float(g.mean()), "compared": len(g)}


def hold(run: Run, name: str, got: dict[str, float]) -> None:
    """Each statistic of the gaps that the cell's file limits under ``name``."""
    for suffix, stat in STATS.items():
        if name + suffix in run.limits:
            run.check(name + suffix, got[stat])


def index_key_gap(run: Run, params: Any, pooled: list[tuple[np.ndarray, np.ndarray]]) -> float:
    """The widest relative error of one position's pooled indexer key against
    the reference's, over ``pooled`` = (token ids, the pool's keys) pairs."""
    reference = modules(run.config)[2]
    worst = 0.0
    for ids, keys in pooled:
        want = np.asarray(reference.index_keys(run.config, params, ids), np.float64)
        off = np.linalg.norm(keys - want, axis=-1) / np.linalg.norm(want, axis=-1)
        worst = max(worst, float(off.max()))
    return worst


def measure(run: Run) -> dict[str, Any]:
    """Set-up, the lead and the window: the end-to-end metric, the exact
    checks, and what the reference is to judge once the engine is freed."""
    traffic = run.traffic
    engine, registry = build_engine(run)
    vocab = run.config["vocab_size"]
    prompts = [schedule.prompt_ids(run.seed, i, n, vocab) for i, n in enumerate(traffic["prompt_tokens"])]
    log(f"{run.cell['name']}: {len(prompts)} sessions, prompts {traffic['prompt_tokens']} ({sum(map(len, prompts))} tokens), "
        f"engine {engine_settings(run)}")
    run.setup.phase("schedule")
    gc.collect()
    gc.freeze()  # the engine's long-lived objects out of the collector's way,
    gc.disable()  # and no collection pause inside a step of the window
    run.setup.phase("gc")
    run.setup.done()

    compiles_before = run.compiles.count
    out = serve(run, engine, registry, prompts)
    records, lead_end, close = out["records"], out["lead_end"], out["close"]
    in_run = out["compiles"] - compiles_before
    run.attempted = len(records)
    run.failed = sum(not rec["tokens"] for rec in records)
    m = stats.serve_metrics(records, lead_end, close) if lead_end < float("inf") else {}
    contexts = [rec["req"].length for rec in records]
    log(f"lead {lead_end:.2f} s (every session's first token + {traffic['lead_after_first_tokens_s']:g} s), window {run.seconds:g} s: "
        f"{len(out['step_s'])} engine steps, {m.get('tokens', 0)} tokens, {m.get('gaps', 0)} gaps; gap p50 {m.get('itl_p50_ms', float('nan')):.3f} "
        f"p95 {m.get('itl_p95_ms', float('nan')):.3f} ms; {m.get('out_tokens_per_s', float('nan')):.1f} tokens/s (a reading); contexts at the close "
        f"{contexts}; {out['ended']} sessions ended or evicted; compiles or cache loads since set-up {in_run}; counters {out['counters']}")
    if "itl_p95_ms" in m:
        run.end_to_end["serve_itl_p95_ms"] = m["itl_p95_ms"]
    run.spans["engine_step_ms"] = [1e3 * s for s in out["step_s"]]
    run.counters.update(out["counters"])
    run.check("requests_without_first_token", run.failed)
    run.check("compiles_in_window", in_run)  # the lead compiles nothing either
    run.check("sessions_ended_or_evicted_before_close", out["ended"])
    run.memory_peak_bytes = memory_peak(run.devices)

    taken = {"served": judged(prompts, out), "probe": out["probe"], "pooled": []}
    for i, keys in out["pooled"].items():  # position t holds token t's key; the last served token's is not written yet
        ids = np.concatenate([prompts[i], np.asarray(records[i]["req"].generated, np.int32)])[: len(keys) - 1]
        taken["pooled"].append((ids, keys[: len(ids)]))
    del engine, records, out
    gc.enable()
    gc.unfreeze()
    gc.collect()
    return taken


def judge(run: Run, taken: dict[str, Any]) -> list[np.ndarray]:
    """What the window served against the plain reference, each number held
    to the limit the cell's file states for it. Returns the reference's
    logits pair by pair, the probe's last, for whoever judges a control or a
    fault against them (``benchmark/keye/tools/faults.py``)."""
    t1 = time.monotonic()
    params = seed_params(run, run.seed)
    served = taken["served"]
    clean = reference_logits(run, params, served)
    got = gap_stats(clean, [tokens for _, tokens in served], f"{len(served)} sessions")
    hold(run, "served_logit_gap", got)
    run.check("served_tokens_short_of_200", max(0, 200 - got["compared"]))
    if any(name.startswith("probe_logit_gap") for name in run.limits):
        probe = [taken["probe"]] if taken["probe"] else []
        clean += reference_logits(run, params, probe)
        hold(run, "probe_logit_gap", gap_stats(clean[len(served):], [tokens for _, tokens in probe], "the probe after the close"))
    if "index_key_gap" in run.limits:
        worst = index_key_gap(run, params, taken["pooled"]) if taken["pooled"] else float("nan")
        log(f"pooled indexer keys of {len(taken['pooled'])} sessions at the first layer: widest relative error of a position's key {worst:.5f}")
        run.check("index_key_gap", worst)
    run.after["reference"] = time.monotonic() - t1
    return clean


def run(run: Run) -> None:
    judge(run, measure(run))
