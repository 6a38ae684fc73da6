"""Flag system: the reference's argparse contract + TPU topology flags.

The reference configures runs through three tiers (SURVEY.md §5.6): torchrun
env vars for topology, argparse for hyperparameters
(``pytorch/resnet/main.py:167-182``, ``pytorch/unet/train.py:310-347``), and
interactive bash prompts that assemble the command (``pytorch/unet/run.sh``).
Here everything is flags (env vars still honored by ``bootstrap.init``), with
the reference's exact flag names and defaults preserved so commands port 1:1.
"""

from __future__ import annotations

import argparse


def add_topology_flags(parser: argparse.ArgumentParser) -> None:
    """Distributed/topology flags — replaces torchrun's CLI + the run.sh
    prompts (``pytorch/unet/run.sh:100-104``)."""
    group = parser.add_argument_group("topology")
    group.add_argument("--coordinator", default=None, help="coordinator addr:port (multi-host; replaces MASTER_ADDR:MASTER_PORT)")
    group.add_argument("--num_processes", type=int, default=None, help="number of host processes (replaces WORLD_SIZE)")
    group.add_argument("--process_id", type=int, default=None, help="this process's id (replaces RANK)")
    group.add_argument("--platform", default=None, choices=("cpu", "tpu"), help="force JAX platform; cpu is the gloo-parity fallback (hello_world.py:44)")
    group.add_argument("--n_virtual_devices", type=int, default=None, help="fake N CPU devices for hardware-free multi-device runs")
    group.add_argument("--dp", type=int, default=-1, help="data-parallel degree (-1: all remaining devices)")
    group.add_argument("--tp", type=int, default=1, help="tensor-parallel degree (model axis)")
    group.add_argument("--pp", type=int, default=1, help="pipeline-parallel degree (pipe axis)")
    group.add_argument("--sp", type=int, default=1, help="sequence-parallel degree (seq axis; ring/ulysses attention)")
    group.add_argument("--ep", type=int, default=1, help="expert-parallel degree (expert axis; MoE)")
    group.add_argument("--zero", action="store_true", help="ZeRO-1: shard optimizer state over the data axis (moments drop to 1/dp per device)")
    group.add_argument("--zero_overlap", action="store_true", help="with --zero: use the explicit bucketed ZeRO-1 schedule (reduce-scattered grad buckets, 1/dp optimizer update, overlapped param all-gather); bit-identical to the GSPMD step where supported, logged fallback otherwise")
    group.add_argument("--tuned_step", default=None, metavar="DB", help="tuning DB (tools/autotune.py --step) whose step|... entry, if present for this model/shape/mesh/dtype, sets remat/grad_accum/overlap; missing or corrupt DB silently keeps the flag defaults")


def ema_decay(value: str) -> float:
    """argparse type for ``--ema``: a decay in [0, 1). 1.0 would freeze the
    average at its random-init seed — training improves while every eval
    silently reports init-quality numbers — so out-of-range fails at parse."""
    f = float(value)
    if not 0.0 <= f < 1.0:
        raise argparse.ArgumentTypeError(
            f"--ema must be in [0, 1), got {f} (it is a decay; 0 disables)"
        )
    return f


def add_training_flags(
    parser: argparse.ArgumentParser,
    *,
    num_epochs: int = 100,
    batch_size: int = 128,
    learning_rate: float = 0.1,
    random_seed: int = 0,
    model_dir: str = "saved_models",
    model_filename: str = "model",
    optimizer: str = "adam",
    weight_decay: float = 0.0,
) -> None:
    """The reference's shared hyperparameter flags, names and defaults intact.

    ResNet defaults: epochs 100, batch 128, lr 0.1, seed 0
    (``pytorch/resnet/main.py:162-176``). UNet callers override to batch 16,
    lr 1e-4, seed 42 (``pytorch/unet/train.py:314-335``). ``--batch_size``
    here is the **global** batch (the reference's is per-process — documented
    difference; one process per host changes the natural unit).
    """
    group = parser.add_argument_group("training")
    group.add_argument("--num_epochs", type=int, default=num_epochs)
    group.add_argument("--batch_size", type=int, default=batch_size, help="GLOBAL batch size")
    group.add_argument("--learning_rate", type=float, default=learning_rate)
    group.add_argument("--optimizer", default=optimizer,
                       choices=("sgd", "adam", "adamw", "adafactor", "lion"),
                       help="default = the reference's choice for this "
                       "trainer (resnet: sgd, unet/lm: adam). adamw/lion use "
                       "decoupled weight decay; adafactor's factored moments "
                       "cut optimizer HBM to ~half of Adam's (composes with "
                       "--zero). --resume requires the same optimizer the "
                       "run started with (opt-state tree mismatch otherwise "
                       "— fail-loud, like --ema)")
    group.add_argument("--weight_decay", type=float, default=weight_decay,
                       help="sgd: coupled L2 (torch semantics, reference "
                       "parity); adamw/adafactor/lion: decoupled decay. "
                       "Ignored by plain adam. Default = the reference's "
                       "value for this trainer (resnet: 1e-5, unet/lm: 0)")
    group.add_argument("--lr_schedule", default="constant",
                       choices=("constant", "cosine", "linear"),
                       help="LR over steps: constant (reference parity), "
                       "warmup+cosine decay, or warmup+linear decay")
    group.add_argument("--warmup_steps", type=int, default=0,
                       help="linear LR warmup from 0 (any --lr_schedule)")
    group.add_argument("--grad_accum", type=int, default=1,
                       help="gradient-accumulation chunks per optimizer step "
                       "(global batch is split evenly; loss-mean semantics "
                       "preserved)")
    group.add_argument("--random_seed", type=int, default=random_seed)
    group.add_argument("--ema", type=ema_decay, default=0.0,
                       help="decay for an exponential moving average of "
                       "params (e.g. 0.999; 0 = off; must be < 1 — at 1.0 "
                       "the average would stay frozen at init). Eval and "
                       "--eval_only then use the averaged weights. The EMA "
                       "rides the checkpoint, so resume/eval/generate runs "
                       "must pass the flag too (tree mismatch otherwise — "
                       "fail-loud)")
    group.add_argument("--model_dir", default=model_dir)
    group.add_argument("--model_filename", default=model_filename)
    group.add_argument("--resume", action="store_true", help="resume from the latest checkpoint in --model_dir (full state: step + optimizer too, unlike the reference's weights-only resume, train.py:342-345)")
    group.add_argument("--eval_only", action="store_true",
                       help="restore the latest checkpoint and run one "
                       "evaluation pass over the eval split, then exit — "
                       "no training (the reference has no standalone eval). "
                       "The train split is still opened (the CLIs build both "
                       "loaders up front); accepted cost for a rare mode")
    group.add_argument("--log_dir", default="logs")
    group.add_argument("--eval_every", type=int, default=10, help="epochs between evals/checkpoints (reference cadence: resnet/main.py:136)")
    group.add_argument("--dtype", default="float32", choices=("float32", "bfloat16"), help="compute dtype (params stay float32)")
    group.add_argument("--profile_dir", default=None, help="write a jax.profiler trace of a few hot steps here (TensorBoard/Perfetto)")
    group.add_argument("--metrics_dir", "--metrics-dir", default=None,
                       help="write telemetry records (per-step scalars, epoch "
                       "stats, MFU/HBM/collective-bytes) as JSONL under this "
                       "directory; render with tools/metrics_report.py")
    group.add_argument("--metrics_every", "--metrics-every", type=int, default=1,
                       help="record every Nth step's scalars to the metrics "
                       "sinks (0 = per-step records off; epoch records always "
                       "flow)")
    group.add_argument("--max_restarts", type=int, default=0, help="auto-resume from the latest checkpoint this many times on failure (0 = fail immediately; the reference's analog is manual restart with --resume)")
    group.add_argument("--restart_delay_s", type=float, default=5.0,
                       help="seconds to wait between auto-resume restarts "
                       "(backoff before re-restoring)")
    group.add_argument("--keep_checkpoints", type=int, default=3,
                       help="retention: keep the last N checkpoints (orbax "
                       "max_to_keep) — bounded history instead of unbounded "
                       "growth; also how far back corrupted-checkpoint "
                       "rollback can reach")
    group.add_argument("--chaos", default=None,
                       help="deterministic fault-injection plan, e.g. "
                       "'nan_grad@step:7,loader_stall@batch:3,kill@step:12,"
                       "corrupt_ckpt@epoch:1' (kinds: nan_grad/kill@step, "
                       "loader_stall/loader_die@batch, corrupt_ckpt@epoch). "
                       "Every fault fires exactly once; recovery is recorded "
                       "in fault_injected_total / recovery_total / "
                       "rollback_total. $DMT_CHAOS is the env fallback. See "
                       "docs/RESILIENCE.md")
    group.add_argument("--guardrails", action="store_true",
                       help="numerics guardrails: judge every step's loss/"
                       "grad-norm/finite scalars through EWMA robust-z "
                       "detectors; tolerated spikes are logged, a poisoned "
                       "verdict rolls back to the pinned last-known-good "
                       "checkpoint and replays (pair with --max_restarts). "
                       "Costs one host sync per step; off (default) adds "
                       "zero syncs and zero allocations. docs/RESILIENCE.md")
    group.add_argument("--digest_every", type=int, default=0,
                       help="with --guardrails: every N steps, sha256 a "
                       "fixed sample of param leaves and publish it on the "
                       "heartbeat for the pod supervisor's cross-rank digest "
                       "vote (a bit-flipped replica is blamed directly; "
                       "minority digest loses). 0 = off")
    group.add_argument("--debug_nans", action="store_true", help="jax_debug_nans: raise at the first NaN-producing op (SURVEY.md §5.2)")
    group.add_argument("--num_workers", type=int, default=None,
                       help="loader fetch threads per host (default: half the "
                       "cores, capped at 16; 0 = synchronous). The reference's "
                       "DataLoader num_workers knob (resnet/main.py:100)")


def add_lm_model_flags(parser: argparse.ArgumentParser) -> "argparse._ArgumentGroup":
    """LM architecture flags shared by ``dmt-train-lm`` and ``dmt-generate``.

    One definition keeps the two entrypoints' defaults byte-identical — the
    checkpoint stores arrays, not architecture, so a silent default drift
    between train and generate would surface as an opaque orbax tree/shape
    mismatch at restore time. Returns the group so callers can append their
    own entrypoint-specific flags (remat, attention, sampling, ...).
    """
    group = parser.add_argument_group("model")
    group.add_argument("--seq_len", type=int, default=512,
                       help="training sequence length (params are RoPE/"
                       "sequence-independent, so inference entrypoints "
                       "accept but ignore it)")
    group.add_argument("--num_layers", type=int, default=4)
    group.add_argument("--num_heads", type=int, default=8)
    group.add_argument("--num_kv_heads", type=int, default=0,
                       help="grouped-query attention: K/V heads shared by "
                       "groups of query heads (0 = num_heads, plain MHA); "
                       "must divide --num_heads. Shrinks the KV cache and "
                       "decode HBM reads by num_heads/num_kv_heads")
    group.add_argument("--head_dim", type=int, default=32)
    group.add_argument("--d_model", type=int, default=256)
    group.add_argument("--d_ff", type=int, default=1024)
    group.add_argument("--moe_experts", type=int, default=0,
                       help="0 = dense SwiGLU MLP; N>1 swaps in a routed MoE "
                       "MLP per block (shard with --ep when training)")
    group.add_argument("--moe_top_k", type=int, default=2)
    group.add_argument("--attention_window", type=int, default=0,
                       help="sliding-window (local) attention: each token "
                       "attends its last N tokens only (0 = full causal). "
                       "A model property — training, prefill, and KV-cached "
                       "decode all honor it (decode then reads O(N) cache "
                       "rows per token). Flash kernels skip out-of-window "
                       "blocks: attention cost becomes O(S*N). Composes "
                       "with --attention ulysses (full-sequence inner) AND "
                       "--attention ring (rotation skipping: each device "
                       "rotates only the O(N/shard) neighbor K/V blocks "
                       "its queries' windows reach)")
    group.add_argument("--moe_routing", default="token_choice",
                       choices=("token_choice", "expert_choice"),
                       help="token_choice = GShard top-k + balance aux loss; "
                       "expert_choice = each expert takes its top-C tokens "
                       "(balanced by construction, but routing sees the "
                       "whole sequence — leaks future context in causal LMs)")
    return group


def save_arch(cfg, ckpt_dir) -> None:
    """Persist the model architecture next to the checkpoint (process 0).

    The checkpoint stores arrays, not architecture; most wrong-flag serving
    mistakes fail loudly anyway (a wrong ``--d_model`` is a shape mismatch,
    a wrong ``--optimizer`` an opt-state tree mismatch). But two knobs are
    TREE-INVISIBLE: ``--attention_window`` and ``--moe_routing`` change
    semantics without changing a single array shape, so serving a
    window-trained checkpoint without the flag would silently decode with
    full attention. ``arch.json`` closes that hole:
    ``arch_mismatch_error`` refuses the mismatch at every start (train,
    resume, eval_only, generate).
    """
    import dataclasses
    from pathlib import Path

    import jax

    if jax.process_index() != 0:
        return
    from deeplearning_mpi_tpu.resilience.integrity import atomic_write_json

    path = Path(ckpt_dir)
    path.mkdir(parents=True, exist_ok=True)
    # Atomic: a kill during the write must not leave a truncated arch.json
    # that poisons every later start with a JSON parse error.
    atomic_write_json(path / "arch.json", dataclasses.asdict(cfg))


def arch_mismatch_error(cfg, ckpt_dir) -> str | None:
    """Formatted refusal message if ``cfg`` differs from the checkpoint
    directory's saved ``arch.json`` — ``None`` if they match or the
    checkpoint predates arch sidecars (old checkpoints keep working; only
    fields present in the file are compared, so new config fields stay
    forward-compatible). One formatter for every caller (train resume,
    eval_only, fresh-train-into-existing-dir, generate), so the message
    and its remedy hint cannot drift between CLIs.

    Multi-host note: all processes read the same file — the checkpoint
    directory is on a shared filesystem by requirement (orbax multi-host
    save/restore already assumes it), so every host reaches the same
    verdict and exits together rather than diverging into a hung
    collective.
    """
    import dataclasses
    import json
    from pathlib import Path

    path = Path(ckpt_dir) / "arch.json"
    if not path.is_file():
        return None
    saved = json.loads(path.read_text())
    # as the file would hold it: a tuple (``layers``) comes back a list
    current = json.loads(json.dumps(dataclasses.asdict(cfg)))
    lines = [
        f"{key}: checkpoint={saved[key]!r}, flags={current[key]!r}"
        for key in saved
        if key in current and saved[key] != current[key]
    ]
    if not lines:
        return None
    return (
        "checkpoint architecture does not match the flags:\n  "
        + "\n  ".join(lines)
        + f"\n(sidecar: {path}; pass matching flags, or use a fresh "
        "--model_dir to train a different architecture)"
    )


def build_lr(args: argparse.Namespace, train_loader) -> object:
    """Resolve the shared LR flags into what ``build_optimizer`` takes.

    ``--lr_schedule constant`` with no warmup stays a bare float (reference
    parity); the decaying schedules span the planned optimizer steps
    (``loader.steps_per_epoch() * --num_epochs``).
    """
    from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

    # --eval_only must build the SAME schedule shape as training: a callable
    # lr gives optax a ScaleByScheduleState(count) opt_state leaf where a
    # bare float gives EmptyState, and the restore template must match the
    # checkpoint's tree structure exactly (the schedule's values are
    # irrelevant to eval — its *state shape* is not).
    return build_lr_schedule(
        args.learning_rate, args.lr_schedule,
        warmup_steps=args.warmup_steps,
        decay_steps=train_loader.steps_per_epoch() * args.num_epochs,
    )


def restore_for_start(args, checkpointer, state, logger):
    """Shared --resume / --eval_only restore; returns (state, start_epoch).

    ``--eval_only`` is resume-or-die: evaluating a fresh random init would
    silently report garbage metrics, so a missing checkpoint is an error.
    ``--resume`` keeps the reference's lenient start-fresh behavior.

    Both paths restore VERIFIED: the newest checkpoint whose integrity
    manifest re-hashes clean, rolling back past corrupted steps
    (``Checkpointer.restore_verified``; ``docs/RESILIENCE.md``).
    """
    from deeplearning_mpi_tpu.resilience.integrity import CheckpointCorruption

    latest = checkpointer.latest_epoch()
    if getattr(args, "eval_only", False):
        if latest is None:
            raise SystemExit(
                f"--eval_only: no checkpoint under {checkpointer.directory}"
            )
        state, epoch = checkpointer.restore_verified(state)
        logger.log(
            f"eval-only: restored verified epoch {epoch} (step {int(state.step)})"
        )
        return state, epoch + 1
    if args.resume:
        if latest is None:
            logger.log(f"--resume: no checkpoint under {checkpointer.directory}; starting fresh")
        else:
            try:
                # Elastic path: the template's shardings describe THIS run's
                # mesh, which need not match the world that saved — a pod
                # re-formed on survivors restores a dp=4/ZeRO checkpoint
                # onto a dp=2 (or dp=1) mesh, orbax re-sharding against the
                # template and the assertion confirming placement landed.
                state, epoch = checkpointer.restore_elastic(state)
            except CheckpointCorruption as err:
                # --resume is lenient about a MISSING checkpoint; stay
                # consistent for an all-corrupt history: warn and start
                # fresh rather than dying on a recoverable situation.
                logger.log(f"--resume: {err}; starting fresh")
                return state, 0
            logger.log(f"resumed from verified epoch {epoch} (step {int(state.step)})")
            return state, epoch + 1
    return state, 0


def build_chaos(args: argparse.Namespace):
    """Resolve ``--chaos`` (or ``$DMT_CHAOS``) into a ChaosInjector, or
    ``None`` when no plan is set — the common case pays one None check.

    The plan is validated against :data:`~..resilience.faults.TRAIN_KINDS`:
    a kind the training workload has no injection hook for (e.g.
    ``serve_crash``) fails loud at parse time instead of silently never
    firing and leaving the reconciliation invariant unbalanced.
    """
    from deeplearning_mpi_tpu.resilience.faults import (
        TRAIN_KINDS,
        ChaosInjector,
        validate_plan_kinds,
    )

    injector = ChaosInjector.from_spec(getattr(args, "chaos", None))
    if injector is not None:
        validate_plan_kinds(
            ",".join(f"{s.kind}@{s.unit}:{s.at}" for s in injector.plan.specs),
            TRAIN_KINDS, workload="training",
        )
    return injector


def build_guardrails(args: argparse.Namespace):
    """Resolve ``--guardrails``/``--digest_every`` into a GuardrailPolicy,
    or ``None`` (the costless-when-off default: no policy object means the
    trainer allocates nothing and adds no host syncs)."""
    if not getattr(args, "guardrails", False):
        return None
    from deeplearning_mpi_tpu.resilience.guardrails import (
        GuardrailConfig,
        GuardrailPolicy,
    )

    return GuardrailPolicy(
        GuardrailConfig(digest_every=getattr(args, "digest_every", 0) or 0)
    )


def setup_runtime(args: argparse.Namespace):
    """Apply topology flags and initialize the runtime. Returns (topology, mesh).

    Import-deferred so flag parsing (--help) never initializes a backend.
    """
    from deeplearning_mpi_tpu.runtime import bootstrap
    from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh

    if args.n_virtual_devices:
        bootstrap.set_virtual_cpu_devices(args.n_virtual_devices)
        args.platform = "cpu"
    topo = bootstrap.init(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        platform=args.platform,
    )
    mesh = create_mesh(
        MeshSpec(
            data=args.dp,
            pipe=getattr(args, "pp", 1),
            expert=getattr(args, "ep", 1),
            seq=getattr(args, "sp", 1),
            model=args.tp,
        )
    )
    if getattr(args, "debug_nans", False):
        from deeplearning_mpi_tpu.utils.profiling import nan_debug_mode

        nan_debug_mode(True)
    return topo, mesh


def build_observability(
    args: argparse.Namespace,
    trainer,
    *,
    flops_per_step: float | None = None,
    issued_flops_per_step: float | None = None,
    comm_bytes_per_step: float | None = None,
) -> None:
    """Attach profiler + heartbeat + telemetry from the shared flags.

    ``--metrics_dir`` adds a JSONL sink to the trainer's registry (every
    record — per-step scalars, epoch stats, evals — lands in
    ``metrics.jsonl`` there; ``tools/metrics_report.py`` renders it).
    ``flops_per_step`` / ``comm_bytes_per_step`` are the CLI's analytic
    estimates (``telemetry.flops`` / ``telemetry.comms``) feeding the
    trainer's MFU and collective-byte epoch stats. When the caller passes no
    comm estimate, the pure-DP gradient all-reduce is derived from the
    trainer's own state + mesh — every data-parallel run gets collective
    accounting for free.
    """
    import os
    import pathlib

    import jax

    from deeplearning_mpi_tpu.resilience.pod import (
        ENV_HEARTBEAT_DIR,
        ENV_HEARTBEAT_INTERVAL,
    )
    from deeplearning_mpi_tpu.train.resilience import Heartbeat
    from deeplearning_mpi_tpu.utils.profiling import Profiler

    if getattr(args, "profile_dir", None):
        trainer.profiler = Profiler(args.profile_dir)
    if getattr(args, "log_dir", None):
        # Under a pod supervisor ($DMT_HEARTBEAT_DIR), each rank beats into
        # its own file in the shared heartbeat dir — the supervisor's
        # pod-level liveness view aggregates them. Standalone runs keep the
        # single heartbeat.json beside the logs.
        hb_dir = os.environ.get(ENV_HEARTBEAT_DIR)
        hb_path = (
            pathlib.Path(hb_dir) / f"heartbeat-{jax.process_index()}.json"
            if hb_dir
            else pathlib.Path(args.log_dir) / "heartbeat.json"
        )
        interval_s = float(os.environ.get(ENV_HEARTBEAT_INTERVAL, "10.0"))
        trainer.heartbeat = Heartbeat(hb_path, interval_s=interval_s).start()
    metrics_dir = getattr(args, "metrics_dir", None)
    if metrics_dir and jax.process_index() == 0:
        # Process 0 only: every rank computes identical global scalars (the
        # records are collective results), so N ranks appending to one
        # metrics.jsonl would duplicate each record N times — and an
        # elastically resumed world would change the duplication factor
        # mid-file, breaking the per-step loss series the parity drills
        # compare.
        from deeplearning_mpi_tpu.telemetry.registry import JsonlSink

        trainer.metrics.add_sink(
            JsonlSink(pathlib.Path(metrics_dir) / "metrics.jsonl")
        )
    trainer.metrics_every = getattr(args, "metrics_every", trainer.metrics_every)
    if flops_per_step is not None:
        trainer.flops_per_step = flops_per_step
    if issued_flops_per_step is not None:
        # Model FLOPs + remat recompute: feeds mfu_issued/mfu_gap (and the
        # overlap-fraction estimate) in the epoch stats. MFU itself stays
        # defined over model FLOPs only (telemetry/flops.py docstring).
        trainer.issued_flops_per_step = issued_flops_per_step
    if comm_bytes_per_step is None and trainer.comm_bytes_per_step is None:
        from deeplearning_mpi_tpu.telemetry import comms

        dp = trainer.mesh.shape.get("data", 1)
        comm_bytes_per_step = comms.dp_grad_allreduce_bytes(
            comms.param_count(trainer.state.params), dp,
            zero=getattr(trainer, "zero", False),
        )
    if comm_bytes_per_step is not None:
        trainer.comm_bytes_per_step = comm_bytes_per_step


def execute_training(
    trainer,
    checkpointer,
    args: argparse.Namespace,
    train_loader,
    eval_loader,
    start_epoch: int,
    state_factory=None,
):
    """Shared CLI tail: fit with optional auto-resume, then clean teardown.

    ``--max_restarts N`` turns crashes into restore-latest-checkpoint-and-
    continue (see ``train.resilience.run_with_auto_resume``); the reference's
    only recovery is a manual re-launch with ``--resume``
    (``pytorch/unet/train.py:342-345``). ``state_factory`` rebuilds a fresh
    initial TrainState for restarts that happen before the first checkpoint —
    required because the jitted step donates the state's buffers, so a crash
    mid-step leaves ``trainer.state`` deleted and unusable.

    Resilience integration (``docs/RESILIENCE.md``): restart restores go
    through ``restore_verified`` (corrupted checkpoints roll back; an
    all-corrupt history restarts from init rather than dying), a SIGTERM
    handler is installed so preemption exits via a graceful final
    checkpoint (``Preempted`` — clean, never retried), and teardown emits
    one ``run_summary`` record carrying every counter — including the
    chaos reconciliation triple — before the sinks close.
    """
    from deeplearning_mpi_tpu.resilience import (
        CheckpointCorruption,
        GracefulShutdown,
        Preempted,
        run_with_auto_resume,
    )

    if getattr(args, "eval_only", False):
        # The CLI upgraded --eval_only to a restore (resume-or-die): by here
        # trainer.state holds checkpoint weights. One collective eval pass.
        try:
            if trainer.profiler is not None:
                trainer.report_eval(
                    {}, note="--profile_dir is a no-op with --eval_only "
                    "(tracing hooks live in the train loop)"
                )
            stats = trainer.evaluate(eval_loader)
            trainer.report_eval(stats)
            return [stats]
        finally:
            if trainer.heartbeat is not None:
                trainer.heartbeat.stop()
            if getattr(trainer, "metrics", None) is not None:
                trainer.metrics.close()

    if args.max_restarts > 0 and state_factory is None:
        # Without a factory, a pre-checkpoint crash would retry on the
        # donated/deleted state and burn every restart on buffer errors.
        raise ValueError("--max_restarts requires a state_factory")

    chaos = getattr(trainer, "chaos", None)
    own_shutdown = trainer.shutdown is None
    if own_shutdown:
        # install() is a no-op off the main thread (degrades to manual
        # request()); every training CLI gets SIGTERM grace for free.
        trainer.shutdown = GracefulShutdown().install()

    attempts = 0

    def fit(restart_epoch: int):
        nonlocal attempts
        attempts += 1
        if attempts > 1:
            pending = getattr(trainer, "pending_rollback", None)
            if pending is not None:
                # Guardrail rollback (docs/RESILIENCE.md): the poisoned
                # steps never happened. Restore the PINNED last-known-good
                # (not merely the newest bytes-clean step, which may carry
                # the poisoned updates), discard younger checkpoints, and
                # replay — the loader order is (seed, epoch)-deterministic,
                # so the replay rejoins the unfaulted trajectory.
                trainer.pending_rollback = None
                template = state_factory() if state_factory else trainer.state
                if checkpointer.latest_epoch() is not None:
                    trainer.state, epoch = checkpointer.rollback_to_last_good(
                        template
                    )
                    restart_epoch = epoch + 1
                else:
                    # Poisoned before the first save: a fresh init IS the
                    # last-known-good.
                    trainer.state = template
                    restart_epoch = 0
                # Rejoin the restored state's step count, so the replayed
                # steps' records/triggers line up with a clean run's.
                trainer._global_step = int(trainer.state.step)
                trainer.place_state()
                trainer.metrics.counter("guard_rollback_total").inc()
                trainer._log(
                    f"guardrail rollback: restored last-good state (step "
                    f"{trainer._global_step}); replaying from epoch "
                    f"{restart_epoch} (poison region {pending.region})"
                )
                return trainer.fit(
                    train_loader, args.num_epochs,
                    eval_loader=eval_loader,
                    start_epoch=max(start_epoch, restart_epoch),
                )
            # Crash restart: the previous state's buffers may be donated/
            # deleted — ALWAYS rebuild, from the newest checkpoint that
            # passes integrity verification when one exists, else from a
            # fresh init (an all-corrupt history restarts from scratch —
            # losing progress beats dying with checkpoints on disk).
            if checkpointer.latest_epoch() is not None:
                template = state_factory() if state_factory else trainer.state
                try:
                    trainer.state, epoch = checkpointer.restore_verified(template)
                    # The VERIFIED epoch wins over the supervisor's
                    # latest+1: a rollback past a corrupted newest step
                    # must re-train the rolled-back epochs, not skip them.
                    restart_epoch = epoch + 1
                except CheckpointCorruption as err:
                    trainer._log(f"restart: {err}; restarting from a fresh init")
                    trainer.state = template  # already a fresh init
                    restart_epoch = 0
            elif state_factory is not None:
                trainer.state = state_factory()
            trainer.place_state()
            if chaos is not None:
                # Surviving the restart IS the kill's recovery (no-op when
                # the crash wasn't an injected kill).
                chaos.record_recovery("kill")
        return trainer.fit(
            train_loader, args.num_epochs,
            eval_loader=eval_loader, start_epoch=max(start_epoch, restart_epoch),
        )

    try:
        if args.max_restarts > 0 and checkpointer is not None:
            return run_with_auto_resume(
                fit, checkpointer,
                max_restarts=args.max_restarts, logger=trainer.logger,
                restart_delay_s=getattr(args, "restart_delay_s", 5.0),
                registry=getattr(trainer, "metrics", None),
            )
        return fit(start_epoch)
    except Preempted as p:
        # Clean preemption: the final checkpoint is on disk; exit 0 so
        # orchestrators reschedule instead of alerting on a crash.
        trainer._log(f"exiting after preemption ({p})")
        return trainer.history
    finally:
        if trainer.heartbeat is not None:
            trainer.heartbeat.stop()
        if trainer.profiler is not None:
            trainer.profiler.stop()  # finalize a trace left open by a crash
        if own_shutdown and trainer.shutdown is not None:
            trainer.shutdown.uninstall()
        if getattr(trainer, "metrics", None) is not None:
            # One run_summary record with every counter/gauge/histogram —
            # where the chaos triple (fault_injected_total == recovery_total
            # + rollback_total) reconciles in the metrics report.
            trainer.metrics.emit("run_summary", trainer.metrics.snapshot())
            if chaos is not None:
                trainer._log(chaos.summary())
            trainer.metrics.close()  # flush + close every telemetry sink
