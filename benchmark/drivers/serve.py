"""Served cells: ``ServingEngine.submit`` and ``ServingEngine.step`` under an
open loop, timed at the benchmark's own client.

The schedule (arrivals and lengths) comes from the traffic file alone; the
seed makes the weights and the token ids. A lead at the same rate runs before
the window, so the window opens in steady state; after it the engine drains.
Every latency is taken from when a request was DUE, not from when the engine
saw it, and a token counts from when ``step`` returned it to the caller.
"""

from __future__ import annotations

import gc
import time
from typing import Any

import numpy as np

from benchmark import check, program, schedule, stats, trace, weights
from benchmark.run import Run, log, memory_peak


class Session:
    """One engine with the seed's weights, warmed up."""

    def __init__(self, run: Run) -> None:
        import jax

        from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine
        from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

        cfg = run.config
        self.cfg = cfg
        self.dtype = program.compute_dtype(cfg)
        self.model = program.model_config(cfg)
        self.engine_cfg = EngineConfig(**{k: v for k, v in cfg["engine"].items() if k != "why"})
        self.make = jax.jit(lambda words: weights.build(cfg, words, self.dtype))
        self.registry = MetricsRegistry()
        self.ServingEngine = ServingEngine
        run.setup.phase("program_imports")
        self.engine = self.fresh_engine(run.seed)
        run.setup.phase("weights_and_engine")
        self.engine.warmup()
        run.setup.phase("warmup")
        self.warm_request(self.engine)
        run.setup.phase("warm_request")

    def warm_request(self, engine: Any) -> None:
        """One request through submit and step before any is timed: two prefill
        chunks, the first token's argmax and a decode step, so that the small
        programs around the engine's three are compiled in set-up too."""
        chunk = self.engine_cfg.prefill_chunk
        engine.submit(np.arange(chunk + 3, dtype=np.int32) % self.cfg["vocab_size"], 3)
        engine.run_until_idle()

    def fresh_engine(self, seed: int) -> Any:
        import jax

        params = self.make(weights.seed_words(seed))
        jax.block_until_ready(params)
        return self.ServingEngine(
            self.model, params, self.engine_cfg, dtype=self.dtype, clock=time.monotonic, registry=self.registry,
        )


def step_work(engine: Any) -> dict[str, Any]:
    """What the next ``step`` will compute, read off the scheduler: the known
    lengths of the rows that decode and the (start, tokens) of each prefill chunk."""
    chunk = engine.engine.prefill_chunk
    decode, prefill = [], []
    for req in engine.scheduler.running():
        if req.state.value == "decode":
            decode.append(req.length)
        elif req.state.value == "prefill":
            prefill.append((req.prefilled, min(chunk, req.prompt_len - req.prefilled)))
    return {"decode": decode, "prefill": prefill}


def serve(run: Run, engine: Any, requests: list[dict[str, Any]], *, seed: int, seconds: float, traced: bool) -> dict[str, Any]:
    """The lead, the window and the drain. Returns the client's record of
    every request and the clock readings around them."""
    import jax

    traffic = run.traffic
    vocab = run.config["vocab_size"]
    lead = traffic["lead_seconds"]
    prompts = [schedule.prompt_ids(seed, i, r["prompt_len"], vocab) for i, r in enumerate(requests)]
    records = [{"due": r["due"], "tokens": [], "req": None, "late": 0.0} for r in requests]
    step_s: list[float] = []
    # Stopping the profiler blocks this loop for most of a second per second
    # traced (starting it costs nothing). So the slice traced is the END of the
    # window, the profiler is stopped once the window has closed, and a traced
    # run's host-clock readings take the requests due before the slice began.
    close = lead + seconds
    trace_at = max(lead, close - traffic["trace_seconds"]) if traced else float("inf")
    tracing = False
    live: list[dict[str, Any]] = []
    nxt = 0
    t0 = time.monotonic()
    give_up = close + traffic["drain_limit_seconds"]
    while True:
        now = time.monotonic() - t0
        while nxt < len(requests) and requests[nxt]["due"] <= now:
            rec = records[nxt]
            rec["req"] = engine.submit(prompts[nxt], requests[nxt]["new_tokens"])
            rec["late"] = now - rec["due"]
            live.append(rec)
            nxt += 1
        if not tracing and trace_at <= now < close:
            trace.start(run.trace_dir)
            tracing = True
        if tracing and now >= close:
            jax.profiler.stop_trace()
            tracing, trace_at = False, float("-inf")
            log(f"profiler stopped in {time.monotonic() - t0 - now:.2f} s, after the window had closed")
        if not live:
            if nxt >= len(requests):
                break
            with jax.profiler.TraceAnnotation("bench/wait_for_arrival"):
                time.sleep(min(0.002, max(0.0, requests[nxt]["due"] - now)))
            continue
        if now > give_up:
            break
        work = step_work(engine) if tracing else None
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            engine.step()
        seen = time.monotonic() - t0
        if lead <= now < close:
            step_s.append(seen - now)
        if work is not None:
            run.work.append(work)
        still = []
        for rec in live:
            req = rec["req"]
            new = len(req.generated) - len(rec["tokens"])
            if new:
                rec["tokens"].extend([seen] * new)
            if req.state.value in ("finished", "shed"):
                continue
            still.append(rec)
        live = still
    if tracing:
        jax.profiler.stop_trace()
    return {"records": records, "step_s": step_s, "t0": t0, "drained_s": time.monotonic() - t0 - close}


def reference_sample(run: Run, records: list[dict[str, Any]], requests: list[dict[str, Any]], seed: int, close: float) -> list[int]:
    """Indices of the finished requests the reference follows: drawn from the
    seed among those the window finished, with the longest in it."""
    done = [
        i for i, rec in enumerate(records)
        if rec["req"] is not None and rec["req"].state.value == "finished" and rec["tokens"] and rec["tokens"][-1] < close
    ]
    if not done:
        return []
    longest = max(done, key=lambda i: requests[i]["prompt_len"] + requests[i]["new_tokens"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 13]))
    rest = [i for i in done if i != longest]
    picked = rng.choice(rest, size=min(run.traffic["reference_requests"] - 1, len(rest)), replace=False)
    return [longest, *(int(i) for i in picked)]


def compare(run: Run, served: list[tuple[np.ndarray, list[int]]], seed: int, *, lower: str | None = None, alter: bool = False) -> tuple[float, int]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over ``served`` = (prompt ids, served tokens) pairs.
    With ``lower`` the tokens judged are those the lower precision puts first
    at each position of the same prompts and tokens (the control); with
    ``alter`` one token in the middle of each request is changed where it was
    produced (the fault)."""
    import jax

    from benchmark import reference

    cfg = run.config
    params = jax.jit(lambda words: weights.build(cfg, words, program.compute_dtype(cfg)))(weights.seed_words(seed))
    worst, compared = 0.0, 0
    for prompt, tokens in served:
        ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(ids))
        logits = np.asarray(reference.serve_logits(cfg, params, ids, rows))
        judged = tokens
        if lower is not None:
            judged = np.asarray(reference.serve_logits(cfg, params, ids, rows, lower=lower)).argmax(axis=-1)
        if alter:
            judged = list(judged)
            judged[len(judged) // 2] = (judged[len(judged) // 2] + 1) % cfg["vocab_size"]
        worst = max(worst, check.served_gap(logits, judged))
        compared += len(tokens)
    del params
    return worst, compared


def run(run: Run) -> None:
    traffic = run.traffic
    session = Session(run)
    run.spans["compile"] = [run.setup.phases["warmup"]]
    engine = session.engine
    requests = schedule.build(traffic, run.seconds)
    lead, close = traffic["lead_seconds"], traffic["lead_seconds"] + run.seconds
    window = [r for r in requests if r["due"] >= lead]
    log(f"{run.cell['name']}: {len(requests)} requests offered ({len(window)} due in the window of {run.seconds:g} s, "
        f"{len(requests) - len(window)} in the lead of {lead:g} s), rate {traffic['rate_per_s']}/s; prompt lengths "
        f"min/median/max {min(r['prompt_len'] for r in requests)}/{int(np.median([r['prompt_len'] for r in requests]))}/"
        f"{max(r['prompt_len'] for r in requests)}, output lengths {min(r['new_tokens'] for r in requests)}/"
        f"{int(np.median([r['new_tokens'] for r in requests]))}/{max(r['new_tokens'] for r in requests)}")
    run.setup.phase("schedule")
    gc.collect()
    gc.freeze()  # the engine's long-lived objects out of the collector's way,
    gc.disable()  # and no collection pause inside a step of the window
    run.setup.phase("gc")
    run.setup.done()

    compiles_before = run.compiles.count
    out = serve(run, engine, requests, seed=run.seed, seconds=run.seconds, traced=run.trace)
    records = out["records"]
    m = stats.serve_metrics(records, lead, close)
    in_window = run.compiles.count - compiles_before
    run.attempted, run.failed = m["attempted"], m["failed"]
    shed = sum(rec["req"] is not None and rec["req"].state.value == "shed" for rec in records)
    late = [rec["late"] for rec in records if rec["req"] is not None]
    calm = close - traffic["trace_seconds"] if run.trace else close  # a traced run's readings stop where its slice begins
    waits = [
        rec["req"].t_admitted - (out["t0"] + rec["due"]) for rec in records
        if rec["req"] is not None and rec["req"].t_admitted is not None and lead <= rec["due"] < calm
    ]
    log(f"window {run.seconds:g} s: {m['attempted']} requests due, {m['attempted'] - m['failed']} got a first token, "
        f"{m['failed']} none, {shed} shed; {m['tokens']} tokens emitted, {m['gaps']} gaps; {len(out['step_s'])} engine steps; "
        f"TTFT mean {m.get('ttft_mean_ms', float('nan')):.1f} p50 {m.get('ttft_p50_ms', float('nan')):.1f} "
        f"p90 {m.get('ttft_p90_ms', float('nan')):.1f} ms; gap p50 {m.get('itl_p50_ms', float('nan')):.1f} "
        f"p95 {m.get('itl_p95_ms', float('nan')):.1f} ms; generator late p99 {1e3 * stats.quantile(late, 0.99):.1f} ms; "
        f"drained {out['drained_s']:.1f} s after the close; compiles or cache loads in window {in_window}")
    if "ttft_mean_ms" in m:
        run.end_to_end["serve_ttft_mean_ms"] = m["ttft_mean_ms"]
        run.spans["ttft_ms"] = [1e3 * (rec["tokens"][0] - rec["due"]) for rec in records if lead <= rec["due"] < calm and rec["tokens"]]
    if "itl_p95_ms" in m:
        run.end_to_end["serve_itl_p95_ms"] = m["itl_p95_ms"]
    run.end_to_end["serve_out_tokens_per_s"] = m["out_tokens_per_s"]
    run.spans["engine_step_ms"] = [1e3 * s for s in out["step_s"]]
    run.spans["queue_wait_ms"] = [1e3 * w for w in waits]
    run.check("requests_without_first_token", m["failed"])
    run.check("compiles_in_window", in_window)
    run.memory_peak_bytes = memory_peak(run.devices)

    # the plain reference over a sample of what the window served, once the engine is freed
    picked = reference_sample(run, records, requests, run.seed, close)
    served = [
        (schedule.prompt_ids(run.seed, i, requests[i]["prompt_len"], run.config["vocab_size"]), list(records[i]["req"].generated))
        for i in picked
    ]
    del engine, out, records
    session.engine = None
    gc.enable()
    gc.unfreeze()
    gc.collect()
    t1 = time.monotonic()
    gap, compared = compare(run, served, run.seed) if served else (float("nan"), 0)
    run.after["reference"] = time.monotonic() - t1
    log(f"reference: {len(served)} requests, {compared} served tokens compared; widest gap {gap:.5f}")
    run.check("served_logit_gap", gap)
    run.check("served_tokens_short_of_200", max(0, 200 - compared))
