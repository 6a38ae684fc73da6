# Developer entry points. `make verify` is the tier-1 gate (the exact
# ROADMAP.md command, byte-for-byte); `make check` adds the telemetry
# report selftest.

SHELL := /bin/bash

.PHONY: verify selftest check smoke chip-smoke lint sanitize-smoke serve-smoke spec-smoke chaos-smoke tune-smoke pod-smoke overlap-smoke fleet-smoke disagg-smoke prefix-smoke autoscale-smoke trace-smoke guard-smoke sim-smoke controlplane-smoke

# Tier-1 tests — verbatim from ROADMAP.md ("Tier-1 verify"). The lint,
# sanitize-smoke, serve-smoke, spec-smoke, chaos-smoke, tune-smoke,
# pod-smoke, overlap-smoke, fleet-smoke, disagg-smoke, and prefix-smoke
# prerequisites gate the tier-1 run on the static analyzer, the
# runtime-sanitizer injection drill, the serving engine's end-to-end
# parity selftest, the speculative-decode parity/reconciliation drill,
# the fault-injection recovery drill, the autotune loop, the elastic-pod
# rank-failure drill, the overlapped-ZeRO-1 bit-equality drill, the
# serving-fleet replica-failure drill, the disaggregated prefill/decode
# drill, the radix prefix-cache drill, the fleet-autoscaler surge drill,
# and the numerics-guardrail drill without touching the ROADMAP command
# itself.
verify: lint sanitize-smoke serve-smoke spec-smoke chaos-smoke tune-smoke pod-smoke overlap-smoke fleet-smoke disagg-smoke prefix-smoke autoscale-smoke trace-smoke guard-smoke sim-smoke controlplane-smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# The chip check: the main path once, at the full width of the 110M LM, on a
# TPU — every Pallas kernel under Mosaic, the LM trainer, the serving
# selftest and the checkpoint hand-off (chip_smoke.py's docstring). It has no
# CPU mode and exits non-zero without a chip, so it is deliberately NOT a
# prerequisite of `verify`. From a sandbox without an accelerator:
#   chiprun -- python chip_smoke.py
chip-smoke:
	python chip_smoke.py

# Static analysis gate (docs/ANALYSIS.md): dmt-lint enforces the repo's
# JAX contracts (donation safety, zero-retrace, atomic IO, single-writer
# JSONL, supervisor ordering, telemetry schema) with AST passes; ruff
# (pinned in pyproject.toml [tool.ruff]) runs alongside when installed —
# the container image does not ship it, so it is gated, not required.
lint:
	env JAX_PLATFORMS=cpu python tools/lint.py
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	else \
		echo "lint: ruff not installed — skipping (CI runs it; config pinned in pyproject.toml)"; \
	fi

# Runtime-sanitizer injection drill (docs/ANALYSIS.md "Runtime
# sanitizer"): under DMT_SANITIZE=1, an injected KV-pool double-free,
# use-after-free, post-warmup retrace, and donation-canary flip must each
# be caught and classified — and the clean paths must trip nothing.
sanitize-smoke:
	env JAX_PLATFORMS=cpu DMT_SANITIZE=1 python tools/sanitize_drill.py

# Telemetry pipeline smoke: registry -> JSONL -> report, no training needed.
selftest:
	env JAX_PLATFORMS=cpu python tools/metrics_report.py --selftest

check: verify selftest

# Continuous-batching serving engine end-to-end: random-init model, Poisson
# trace, every completion verified token-for-token against offline greedy
# decode (docs/SERVING.md).
serve-smoke:
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.serve_lm \
		--selftest --num_layers 2 --num_heads 2 --head_dim 16 \
		--d_model 64 --d_ff 128 --num_requests 8 --rate 100 \
		--max_new_tokens 8 --prompt_len_min 3 --prompt_len_max 20 \
		--max_slots 3 --block_size 8 --num_blocks 32 \
		--max_blocks_per_seq 6 --prefill_chunk 8

# Speculative decoding end-to-end: same trace as serve-smoke but with a
# 1-layer self-draft proposing 3 tokens/step and bucketed decode-batch
# formation. The selftest asserts bit-identical greedy parity (the
# exact-match acceptance rule means the draft can never change output),
# counter reconciliation (proposed == accepted + rolled back), and a
# nonzero acceptance rate (docs/SERVING.md "Speculative decoding").
spec-smoke:
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.serve_lm \
		--selftest --num_layers 2 --num_heads 2 --head_dim 16 \
		--d_model 64 --d_ff 128 --num_requests 8 --rate 100 \
		--max_new_tokens 8 --prompt_len_min 3 --prompt_len_max 20 \
		--max_slots 3 --block_size 8 --num_blocks 32 \
		--max_blocks_per_seq 6 --prefill_chunk 8 \
		--spec_k 3 --draft_layers 1 --decode_buckets 2,3

# Overlapped-ZeRO-1 bit-equality drill (docs/PERF_ANALYSIS.md): 5 training
# steps at dp=2 (two virtual CPU devices) through the explicit bucketed
# reduce-scatter/all-gather schedule vs the GSPMD ZeRO-1 path — losses,
# optimizer state, and params must be BIT-identical (no tolerance).
overlap-smoke:
	env JAX_PLATFORMS=cpu python tools/overlap_drill.py

# Compilation-service acceptance loop (docs/COMPILATION.md): autotune tiny
# kernels into a tuning DB, round-trip it, verify tuned == default
# numerics, and prove a warm-started serving engine hits the persistent
# compile cache and performs zero compiles on its first request.
tune-smoke:
	env JAX_PLATFORMS=cpu python tools/autotune.py --selftest

# 30-second observability demo: tiny CPU-mesh LM run with telemetry on,
# rendered by the report tool (docs/OBSERVABILITY.md walks through it).
smoke:
	rm -rf /tmp/dmt_smoke
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.train_lm \
		--n_virtual_devices 8 --num_epochs 1 --batch_size 16 \
		--train_sequences 64 --seq_len 64 --num_layers 2 --d_model 64 \
		--d_ff 128 --num_heads 4 --head_dim 16 --eval_every 1 \
		--metrics_dir /tmp/dmt_smoke/metrics --log_dir /tmp/dmt_smoke/logs \
		--model_dir /tmp/dmt_smoke/models
	python tools/metrics_report.py /tmp/dmt_smoke/metrics/metrics.jsonl

# Fault-injection recovery drill (<60s, docs/RESILIENCE.md): a tiny LM run
# where the epoch-1 checkpoint is corrupted on disk and the process "dies"
# mid-epoch-2; auto-resume must roll back past the corruption to the
# verified epoch-0 checkpoint, re-train, and finish all 3 epochs. The
# follow-up assert reads the run's own metrics.jsonl and requires the
# reconciliation invariant: fault_injected_total == recovery_total +
# rollback_total.
chaos-smoke:
	rm -rf /tmp/dmt_chaos
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.train_lm \
		--n_virtual_devices 8 --num_epochs 3 --batch_size 8 \
		--train_sequences 40 --seq_len 32 --num_layers 1 --d_model 32 \
		--d_ff 64 --num_heads 2 --head_dim 16 --eval_every 1 \
		--max_restarts 2 --restart_delay_s 0.1 \
		--chaos "corrupt_ckpt@epoch:1,kill@step:11" \
		--metrics_dir /tmp/dmt_chaos/metrics \
		--model_dir /tmp/dmt_chaos/models --log_dir /tmp/dmt_chaos/logs
	env JAX_PLATFORMS=cpu python -c 'import json; recs = [json.loads(l) for l in open("/tmp/dmt_chaos/metrics/metrics.jsonl")]; s = [r for r in recs if r["kind"] == "run_summary"][-1]; f, r, b = (s.get(k, 0) for k in ("fault_injected_total", "recovery_total", "rollback_total")); assert f >= 2 and f == r + b, (f, r, b); print("chaos-smoke OK: injected=%d recovered=%d rolled_back=%d" % (f, r, b))'

# Elastic-pod rank-failure drill (docs/RESILIENCE.md "Elastic pods",
# docs/TPU_POD_RUNBOOK.md): a 2-process CPU pod loses rank 1 to a planned
# rank_kill mid-epoch-1; the supervisor must detect it, re-form a world of
# one, resume from the epoch-0 checkpoint, and land on a loss trajectory
# bit-identical to a clean single-process from-checkpoint run — with the
# pod-level chaos books reconciling in pod_metrics.jsonl.
pod-smoke:
	env JAX_PLATFORMS=cpu python tools/pod_drill.py --fault rank_kill \
		--root /tmp/dmt_pod_smoke

# Numerics-guardrail drill (docs/RESILIENCE.md "Numerics guardrails"):
# both arms of tools/guardrail_drill.py. loss_spike — a planned x1000
# loss scale must draw a poisoned verdict, roll back to the pinned
# last-known-good checkpoint, and replay onto a trajectory bit-identical
# to an unfaulted run. bitflip — a 2-process pod's rank 1 flips one
# param bit mid-run; the supervisor's cross-rank digest vote must convict
# it, quarantine the host, prune poisoned checkpoints, and re-form a
# world of one whose resumed losses are bit-identical to a clean resume.
# Chaos books must reconcile in both arms.
guard-smoke:
	env JAX_PLATFORMS=cpu python tools/guardrail_drill.py --arm both \
		--root /tmp/dmt_guard_smoke

# Disaggregated prefill/decode drill (docs/SERVING.md "Disaggregated
# topology"): the serve-smoke trace through the split topology — a
# prefill-only engine handing completed prompts to a decode-only engine
# over one shared KV pool — under a handoff_stall + serve_crash chaos
# plan. The selftest asserts every stream is still bit-identical to
# offline greedy (the handoff and both recoveries must be invisible in
# the tokens); the second run gates the opt-in int8 paged KV cache on
# measured token-level acceptance vs the fp reference.
disagg-smoke:
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.serve_lm \
		--selftest --disagg --warmup \
		--chaos "handoff_stall@step:6,serve_crash@step:14" \
		--num_layers 2 --num_heads 2 --head_dim 16 \
		--d_model 64 --d_ff 128 --num_requests 8 --rate 100 \
		--max_new_tokens 8 --prompt_len_min 3 --prompt_len_max 20 \
		--max_slots 3 --block_size 8 --num_blocks 32 \
		--max_blocks_per_seq 6 --prefill_chunk 8
	env JAX_PLATFORMS=cpu python -m deeplearning_mpi_tpu.cli.serve_lm \
		--selftest --disagg --kv_dtype int8 \
		--num_layers 2 --num_heads 2 --head_dim 16 \
		--d_model 64 --d_ff 128 --num_requests 8 --rate 100 \
		--max_new_tokens 8 --prompt_len_min 3 --prompt_len_max 20 \
		--max_slots 3 --block_size 8 --num_blocks 32 \
		--max_blocks_per_seq 6 --prefill_chunk 8

# Radix prefix-cache drill (docs/SERVING.md "Prefix cache &
# multi-tenancy"): a two-tenant trace whose prompts share a long,
# non-block-aligned preamble through a colocated engine with the radix
# cache on and per-tenant budgets. Asserts prefix hits and CoW copies
# fire, every stream stays bit-identical to offline greedy, the
# over-budget tenant is shed with reason tenant_budget, and the pool's
# refcount books balance at drain (flush() returns every block).
prefix-smoke:
	env JAX_PLATFORMS=cpu python tools/prefix_drill.py

# Serving-fleet replica-failure drill (docs/SERVING.md "Fault-tolerant
# fleet", docs/TPU_POD_RUNBOOK.md §8): a 2-replica CPU fleet under a
# trace-replay burst loses replica 0 to a planned replica_kill and
# replica 1 to a replica_hang; the supervisor must re-dispatch every
# in-flight request to a survivor (original arrival/deadline preserved),
# respawn both, and roll a zero-downtime weight swap through the fleet —
# with every completed stream bit-identical to offline greedy, zero
# dropped requests, zero post-warmup compiles, and the chaos books
# reconciled in fleet_metrics.jsonl.
fleet-smoke:
	env JAX_PLATFORMS=cpu python tools/fleet_drill.py --fault kill_hang \
		--root /tmp/dmt_fleet_smoke

# Fleet-autoscaler surge drill (docs/SERVING.md "Load-adaptive
# autoscaling", docs/TPU_POD_RUNBOOK.md §9): a 1-replica fleet under a
# burst+spike trace must scale up (supervised spawn, warmed and
# ready-acked before the router sees it) while a planned SIGKILL races the
# first scale-up, then drain-retire back toward the floor on the trickle
# tail — zero drops, every completed stream bit-identical to offline
# greedy, and the scale books reconciling
# (scale_events == spawned + retired + vetoed). The brownout ladder has
# its own drill mode (--fault brownout); the smoke runs surge only to
# keep the verify gate fast.
autoscale-smoke:
	env JAX_PLATFORMS=cpu python tools/autoscale_drill.py --fault surge \
		--root /tmp/dmt_autoscale_smoke

# Control-plane crash drill (docs/RESILIENCE.md "Control-plane crash
# safety", docs/TPU_POD_RUNBOOK.md §12): the fleet SUPERVISOR is
# SIGKILLed mid-surge (load_spike live, a scale-up warming), its
# orphaned replicas keep decoding headless, one orphan is killed, and a
# restarted supervisor must replay the write-ahead journal, re-adopt
# every live replica without respawning it (serve_compile_total flat —
# zero retraces), respawn the corpse, re-dispatch its orphaned requests
# with their original arrival/deadline, and drain with zero drops —
# every stream bit-identical to offline greedy and the chaos + scale
# books reconciling across both incarnations in fleet_metrics.jsonl.
controlplane-smoke:
	env JAX_PLATFORMS=cpu python tools/controlplane_drill.py \
		--root /tmp/dmt_controlplane_smoke

# Load-simulator drill (docs/SIMULATION.md): three phases. scale — a
# >=100k-request multi-tenant compressed day (diurnal + bursts + flash
# crowd + an adversarial tenant) simulated against the REAL
# router/scheduler/autoscaler objects under the fake clock in <60s on
# CPU, books balanced (completed + shed == requests), byte-deterministic
# twice. sweep — a seeded policy-parameter search scored on SLO-attained
# completions per replica-second; the winner must round-trip through the
# autotune TuningDB under its simpolicy|<digest> key. predictive — a
# REAL-process fleet with the predictive autoscaler replays a
# flash-crowd trace; the forecaster must fire the first scale-up BEFORE
# the crowd's peak, with zero dropped requests and reconciled scale
# books.
sim-smoke:
	env JAX_PLATFORMS=cpu python tools/sim_drill.py --phase all \
		--root /tmp/dmt_sim_smoke

# Distributed-tracing drill (docs/OBSERVABILITY.md "Distributed request
# tracing"): a 2-replica disaggregated fleet replays a trace with the
# flight recorder armed while chaos kills replica 0 mid-decode. The
# merged per-process JSONL (tools/trace_report.py) must cover every
# completed request — queue+prefill+handoff+decode+stream spans within
# 5% of measured TTLT — with zero orphan spans, and the killed replica
# must leave its flight dump behind. A short traced training run then
# proves the per-phase step attribution tiles the epoch wall-clock and
# mfu_gap decomposes into named phase shares.
trace-smoke:
	env JAX_PLATFORMS=cpu python tools/trace_drill.py \
		--root /tmp/dmt_trace_smoke
