"""Training cells: ``Trainer``'s jitted step through its epoch loop, on batches
from ``data/loader.py``.

Set-up builds ONE trainer with its state (weights from the seed), compiles its
step, and drives it through its first steps by the same call and feed the
window uses; the window then carries on with that same object. The plain
reference follows the first steps after the window has closed.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Iterator

import numpy as np

from benchmark import check, program, trace, weights
from benchmark.run import Run, log, memory_peak


class _StderrLogger:
    """The trainer prints through this, so that standard output stays the result's."""

    def log(self, msg: str) -> None:
        log(f"trainer: {msg}")


class RandomTokens:
    """Rows of token ids drawn from ``--seed``: row ``i`` from (seed, i), so
    all rows differ and the same seed gives the same rows."""

    def __init__(self, rows: int, seq_len: int, vocab: int, seed: int) -> None:
        self.rows, self.seq_len, self.vocab, self.seed = rows, seq_len, vocab, seed

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, index: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 11, int(index)]))
        return {"tokens": rng.integers(0, self.vocab, self.seq_len, dtype=np.int64).astype(np.int32)}


class Feed:
    """What ``Trainer.run_epoch`` is given as its loader: the program's own
    ``ShardedLoader``, stopped after a number of steps or at a deadline, never
    more than the prefetch depth ahead of the device, and timed.

    ``loader_s`` is the time spent inside the program's loader; ``exposed_s``
    the part of it after which the device had already finished everything
    dispatched (so it was idle, waiting for this batch); ``device_s`` the time
    the feed waited for the device instead.
    """

    def __init__(self, loader: Any, watch: "StepWatch") -> None:
        self.loader, self.watch = loader, watch
        self.max_steps: int | None = None
        self.deadline: float | None = None
        self.keep_rows = False
        self.rows: list[np.ndarray] = []
        self.loader_s = self.exposed_s = self.device_s = 0.0
        self.first_yield: float | None = None
        self.yielded = 0

    def epoch(self, epoch: int) -> Iterator[Any]:
        import jax

        inner = self.loader.epoch(epoch)
        steps = 0
        try:
            while self.max_steps is None or steps < self.max_steps:
                if self.deadline is not None and time.monotonic() >= self.deadline:
                    break
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation("bench/loader_next"):
                    batch = next(inner)
                t1 = time.monotonic()
                last = self.watch.last
                starved = last is None or last.is_ready()
                if last is not None:
                    last.block_until_ready()
                t2 = time.monotonic()
                self.loader_s += t1 - t0
                self.device_s += t2 - t1
                if starved and steps:
                    self.exposed_s += t1 - t0
                if self.keep_rows:
                    self.rows.append(np.asarray(batch["tokens"]))
                if self.first_yield is None:
                    self.first_yield = t2
                steps += 1
                self.yielded += 1
                yield batch
        finally:
            inner.close()


class StepWatch:
    """A sink and a view of the trainer's registry: the newest dispatched
    step's loss (still on the device) and every flushed step's loss."""

    def __init__(self, registry_cls: type) -> None:
        watch = self

        class Watched(registry_cls):  # type: ignore[misc, valid-type]
            def record_step(self, step: int, scalars: Any) -> None:
                watch.last = scalars["loss"]
                super().record_step(step, scalars)

        self.last: Any = None
        self.losses: list[float] = []
        self.registry = Watched(sinks=[self])

    def write(self, record: dict[str, Any]) -> None:
        if record.get("kind") == "step":
            self.losses.append(float(record["loss"]))

    def close(self) -> None:
        pass


class Session:
    """One trainer with its state, its loader and its feed."""

    def __init__(self, run: Run) -> None:
        import jax

        from deeplearning_mpi_tpu.data import ShardedLoader
        from deeplearning_mpi_tpu.models import TransformerLM
        from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh
        from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry
        from deeplearning_mpi_tpu.train import Trainer, TrainState
        from deeplearning_mpi_tpu.train.trainer import build_optimizer

        cfg, traffic, tr = run.config, run.traffic, run.config["train"]
        self.cfg = cfg
        self.mesh = create_mesh(MeshSpec(data=run.chips), devices=list(run.devices))
        attention_fn = None
        if tr["attention"] == "flash":
            from deeplearning_mpi_tpu.parallel import make_flash_attention_fn

            attention_fn = make_flash_attention_fn(self.mesh)
        model = TransformerLM(config=program.model_config(cfg), dtype=program.compute_dtype(cfg), attention_fn=attention_fn)
        self.tx = build_optimizer(tr["optimizer"], tr["learning_rate"], clip_norm=tr["clip_norm"])
        self.apply_fn = model.apply
        self.TrainState = TrainState
        run.setup.phase("program_imports")

        self.rows = traffic["rows_per_chip"] * run.chips
        self.seq_len = traffic["seq_len"]
        self.watch = StepWatch(MetricsRegistry)
        self.trainer = Trainer(
            self.fresh_state(run.seed), "lm", self.mesh, clip_norm=tr["clip_norm"],
            metrics=self.watch.registry, logger=_StderrLogger(),
        )
        self.trainer.place_state()
        jax.block_until_ready(self.trainer.state.params)
        run.setup.phase("weights")

        self.ShardedLoader = ShardedLoader
        self.feed = Feed(self.loader(run.seed), self.watch)
        self.epochs = 0
        run.setup.phase("data")

    def loader(self, seed: int) -> Any:
        dataset = RandomTokens(self.rows * 4096, self.seq_len, self.cfg["vocab_size"], seed)
        return self.ShardedLoader(dataset, self.rows, self.mesh, shuffle=True, seed=seed % (2**31), num_workers=2)

    def reseed(self, seed: int) -> None:
        """The same trainer and compiled step on another seed's weights and rows
        (for reading many seeds in one process)."""
        self.trainer.state = self.fresh_state(seed)
        self.trainer.place_state()
        self.feed.loader = self.loader(seed)

    def fresh_state(self, seed: int) -> Any:
        """The state at step 0 from the seed's weights, in one jitted call, born
        where the program's placement rules put it (replicated over ``data``)."""
        import jax
        import jax.numpy as jnp

        from deeplearning_mpi_tpu.parallel import infer_state_sharding

        cfg, tx = self.cfg, self.tx

        def make(words: Any) -> Any:
            params = weights.build(cfg, words, jnp.float32)
            return self.TrainState(
                step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
                opt_state=tx.init(params), apply_fn=self.apply_fn, tx=tx,
            )

        words = weights.seed_words(seed)
        shardings = infer_state_sharding(jax.eval_shape(make, words), self.mesh, zero=False)
        return jax.jit(make, out_shardings=shardings)(words)

    def steps(self, *, max_steps: int | None = None, seconds: float | None = None) -> tuple[int, float]:
        """One ``run_epoch`` over the feed; returns (steps done, seconds from
        the first batch to the last step's end)."""
        feed = self.feed
        feed.max_steps, feed.first_yield = max_steps, None
        feed.deadline = None if seconds is None else time.monotonic() + seconds
        before = feed.yielded
        self.trainer.run_epoch(feed, self.epochs)  # ends with a host sync on the last step
        end = time.monotonic()
        self.epochs += 1
        return feed.yielded - before, end - (feed.first_yield or end)

    def first_grad_norms(self) -> np.ndarray:
        """Per-leaf norm of the first gradient as Adam got it, from the first
        moment after one step: ``mu = (1 - b1) * g``."""
        import jax

        from benchmark.reference import leaf_norms_jit

        adam = [s for s in jax.tree.leaves(self.trainer.state.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
        return np.asarray(leaf_norms_jit(adam[0].mu)) / (1.0 - self.cfg["train"]["adam_b1"])

    def change_norms(self, seed: int) -> np.ndarray:
        return np.asarray(weights.change_norms(self.cfg, weights.seed_words(seed), self.trainer.state.params))

    def first_steps(self, seed: int, n: int) -> dict[str, Any]:
        """Drive the trainer through its first ``n`` steps and read what the
        reference is compared with."""
        self.watch.losses.clear()
        self.feed.rows.clear()
        self.feed.keep_rows = True
        self.steps(max_steps=1)
        out: dict[str, Any] = {"first_grad": self.first_grad_norms()}
        if n > 1:
            self.steps(max_steps=n - 1)
        self.feed.keep_rows = False
        out["change"] = self.change_norms(seed)
        out["loss"] = list(self.watch.losses)
        out["batches"] = list(self.feed.rows)
        return out

    def free(self) -> None:
        self.trainer.state = None
        self.watch.last = None
        self.trainer.metrics.drop_pending_steps()
        gc.collect()


def compare(run: Run, program: dict[str, Any], reference: dict[str, Any]) -> None:
    """Hold each compared number to its limit. A number the cell's file lists
    under ``not_compared`` (no fault or control separates it from sound runs,
    PERF.md section 2) is printed as a reading."""
    gaps, note = check.training(program, reference)
    log(note)
    readings = run.manifest.cell_file(run.cell["name"]).get("not_compared", [])
    for name, value in gaps.items():
        if name in readings:
            log(f"reading {name} = {value:.6g} (not compared)")
        else:
            run.check(name, value)


def run(run: Run) -> None:
    import jax

    traffic = run.traffic
    session = Session(run)
    trainer, feed = session.trainer, session.feed

    # compile, or load from the cache, the one step program the window uses
    sample_feed = feed.loader.epoch(10**6)
    sample = next(sample_feed)
    sample_feed.close()
    before = run.compiles.count
    t0 = time.monotonic()
    trainer.warmup(sample)
    run.spans["compile"] = [time.monotonic() - t0]
    run.setup.phase("compile_or_load")

    n_first = traffic["first_steps"]
    program = session.first_steps(run.seed, n_first)
    flat = np.concatenate(program["batches"]).reshape(-1, session.seq_len)
    run.check("rows_repeated_in_first_steps", len(flat) - len({r.tobytes() for r in flat}))
    log(f"{run.cell['name']}: {n_first} first steps done, losses {[round(x, 5) for x in program['loss']]}; "
        f"global batch {session.rows} row(s) of {session.seq_len} item(s)")
    run.setup.phase("first_steps")
    gc.collect()
    run.setup.phase("gc")
    run.setup.done()

    # the window
    compiles_before = run.compiles.count
    feed.loader_s = feed.exposed_s = feed.device_s = 0.0
    if run.trace:
        lead = max(0.0, (run.seconds - traffic["trace_seconds"]) / 2)
        steps_a, secs_a = session.steps(seconds=lead) if lead > 0.5 else (0, 0.0)
        trace.start(run.trace_dir)
        steps_t, secs_t = session.steps(seconds=traffic["trace_seconds"])
        jax.profiler.stop_trace()
        rest = run.seconds - secs_a - secs_t
        steps_b, secs_b = session.steps(seconds=rest) if rest > 0.5 else (0, 0.0)
        steps, seconds = steps_a + steps_t + steps_b, secs_a + secs_t + secs_b
    else:
        steps, seconds = session.steps(seconds=run.seconds)
    items = steps * session.rows * session.seq_len
    run.attempted, run.failed = steps, 0
    run.end_to_end["train_items_per_s_per_chip"] = items / seconds / run.chips
    run.counters.update(window_s=seconds, loader_exposed_s=feed.exposed_s)
    in_window = run.compiles.count - compiles_before
    log(f"window {seconds:.3f} s: {steps} steps of {session.rows * session.seq_len} items over {run.chips} chip(s); "
        f"loss {session.watch.losses[n_first]:.4f} -> {session.watch.losses[-1]:.4f}; feed waited "
        f"{feed.device_s:.3f} s for the device, {feed.loader_s:.3f} s in the loader ({feed.exposed_s:.3f} s exposed); "
        f"compiles or cache loads in window {in_window}, before it {compiles_before - before}")
    run.check("compiles_in_window", in_window)
    run.check("steps_short_of_one", 0 if steps >= 1 else 1)
    from deeplearning_mpi_tpu.runtime.mesh import occupied_devices

    run.check("chips_without_state", run.chips - occupied_devices(trainer.state.params))
    run.memory_peak_bytes = memory_peak(run.devices)

    # the plain reference, once the program's state is freed
    session.free()
    t0 = time.monotonic()
    from benchmark import reference

    ref = reference.follow_training(run.config, run.seed, program["batches"], devices=run.devices)
    run.after["reference"] = time.monotonic() - t0
    log(f"reference: followed {n_first} steps, losses {[round(x, 5) for x in ref['loss']]}")
    compare(run, program, ref)
