"""Profiling, step timing, collective latency, and resilience subsystems."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_mpi_tpu.train.resilience import (
    Heartbeat,
    TrainingFailure,
    preflight,
    run_with_auto_resume,
)
from deeplearning_mpi_tpu.utils.profiling import (
    Profiler,
    StepTimer,
    measure_collective_latency,
)


class TestStepTimer:
    def test_times_steps_and_summarizes(self):
        timer = StepTimer(sync_every=4)
        x = jnp.zeros((8, 8))
        step = jax.jit(lambda a: a @ a + 1.0)
        out = step(x)
        timer.tick(out)  # window start
        for _ in range(8):
            out = step(out)
            timer.tick(out)
        s = timer.summary(items_per_step=32)
        assert s["steps_timed"] == 8
        assert s["step_ms_p50"] > 0
        assert s["items_per_s"] > 0
        assert s["items_per_s_per_device"] == pytest.approx(
            s["items_per_s"] / jax.device_count()
        )

    def test_empty_summary(self):
        assert StepTimer().summary() == {}

    def test_short_run_flushes_partial_window(self):
        """Fewer steps than sync_every must still produce stats (summary
        flushes the pending window)."""
        timer = StepTimer(sync_every=10)
        x = jnp.ones((4, 4))
        step = jax.jit(lambda a: a + 1.0)
        out = step(x)
        timer.tick(out)
        for _ in range(3):
            out = step(out)
            timer.tick(out)
        s = timer.summary()
        assert s["steps_timed"] == 3
        assert s["step_ms_p50"] > 0


class TestProfiler:
    def test_trace_writes_files(self, tmp_path):
        prof = Profiler(tmp_path / "trace")
        step = jax.jit(lambda a: a * 2.0)
        out = prof.trace_steps(step, jnp.ones((4,)), num_steps=2)
        np.testing.assert_allclose(np.asarray(out), 2.0)
        files = list((tmp_path / "trace").rglob("*"))
        assert files, "profiler trace produced no files"

    def test_disabled_profiler_is_noop(self):
        prof = Profiler(None)
        with prof:
            pass  # no trace dir: start/stop must be no-ops


class TestCollectiveLatency:
    def test_measures_allreduce_on_mesh(self, mesh):
        out = measure_collective_latency(mesh, num_floats=1 << 12, trials=3)
        assert out["axis_size"] == 8
        assert out["all_reduce_ms_min"] > 0
        assert out["bus_gbps"] > 0


class TestAutoResume:
    def test_retries_from_checkpoint_then_succeeds(self):
        calls = []

        class FakeCkpt:
            def latest_epoch(self):
                return 3

        def fit(start_epoch):
            calls.append(start_epoch)
            if len(calls) < 3:
                raise RuntimeError("boom")
            return "done"

        out = run_with_auto_resume(
            fit, FakeCkpt(), max_restarts=3, restart_delay_s=0.0,
            logger=type("L", (), {"log": staticmethod(lambda m: None)})(),
        )
        assert out == "done"
        assert calls == [0, 4, 4]  # restarts resume at checkpoint epoch + 1

    def test_exhausted_budget_raises_loudly(self):
        class FakeCkpt:
            def latest_epoch(self):
                return None

        def fit(start_epoch):
            raise RuntimeError("persistent failure")

        with pytest.raises(TrainingFailure):
            run_with_auto_resume(
                fit, FakeCkpt(), max_restarts=1, restart_delay_s=0.0,
                logger=type("L", (), {"log": staticmethod(lambda m: None)})(),
            )


class TestHeartbeat:
    def test_writes_progress_json(self, tmp_path):
        path = tmp_path / "hb.json"
        hb = Heartbeat(path, interval_s=0.05)
        with hb:
            hb.progress = {"epoch": 2, "step": 17}
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if path.exists() and "step" in path.read_text():
                    break
                time.sleep(0.05)
        payload = json.loads(path.read_text())
        assert payload["step"] == 17
        assert payload["process_index"] == 0

    def test_stop_is_idempotent(self, tmp_path):
        hb = Heartbeat(tmp_path / "hb.json", interval_s=0.05).start()
        hb.stop()
        hb.stop()


class TestRunLoggerMetrics:
    def test_jsonl_sidecar(self, tmp_path):
        import json

        from deeplearning_mpi_tpu.utils.logging import RunLogger

        logger = RunLogger(tmp_path, echo=False, run_name="run")
        logger.log_metrics({"kind": "epoch", "epoch": 0, "loss": 1.25})
        logger.log_metrics({"kind": "epoch", "epoch": 1, "loss": 1.0})
        records = [
            json.loads(line)
            for line in (tmp_path / "run.metrics.jsonl").read_text().splitlines()
        ]
        assert [r["epoch"] for r in records] == [0, 1]
        assert records[0]["loss"] == 1.25
        assert all("ts" in r and r["kind"] == "epoch" for r in records)

    def test_disabled_without_log_dir(self):
        from deeplearning_mpi_tpu.utils.logging import RunLogger

        RunLogger(None, echo=False).log_metrics({"loss": 1.0})  # no-op, no crash


class TestPreflight:
    def test_missing_data_dir_fails_with_message(self, tmp_path):
        with pytest.raises(SystemExit, match="data directory"):
            preflight(data_dir=str(tmp_path / "nope"))

    def test_creates_model_and_log_dirs(self, tmp_path):
        preflight(model_dir=str(tmp_path / "m"), log_dir=str(tmp_path / "l"))
        assert (tmp_path / "m").is_dir() and (tmp_path / "l").is_dir()

    def test_batch_divisibility(self, mesh):
        with pytest.raises(SystemExit, match="divisible"):
            preflight(global_batch_size=12, mesh=mesh)
        preflight(global_batch_size=16, mesh=mesh)  # ok

    def test_grad_accum_divisibility(self, mesh):
        # 8-device data axis: batch 32 / grad_accum 5 doesn't divide; 32/8
        # divides the batch but leaves per-chunk 4 < dp 8.
        with pytest.raises(SystemExit, match="grad_accum 5"):
            preflight(global_batch_size=32, mesh=mesh, grad_accum=5)
        with pytest.raises(SystemExit, match="per-chunk batch"):
            preflight(global_batch_size=32, mesh=mesh, grad_accum=8)
        preflight(global_batch_size=32, mesh=mesh, grad_accum=2)  # ok


class TestExecuteTraining:
    """The CLI tail: donated-state rebuild on pre-checkpoint crashes."""

    def _make(self, fail_times, latest=None):
        import argparse

        calls = {"fit": 0, "factory": 0, "restore": 0, "placed": 0}

        class FakeTrainer:
            heartbeat = None
            profiler = None
            shutdown = None
            logger = type("L", (), {"log": staticmethod(lambda m: None)})()
            state = "initial"

            def place_state(self):
                calls["placed"] += 1

            def fit(self, loader, num_epochs, eval_loader=None, start_epoch=0):
                calls["fit"] += 1
                if calls["fit"] <= fail_times:
                    raise RuntimeError("crash")
                return "done"

        class FakeCkpt:
            def latest_epoch(self):
                return latest

            def restore_verified(self, template):
                calls["restore"] += 1
                return "restored", latest

        def state_factory():
            calls["factory"] += 1
            return "fresh"

        args = argparse.Namespace(num_epochs=5, max_restarts=2)
        return FakeTrainer(), FakeCkpt(), args, state_factory, calls

    def test_precheckpoint_crash_rebuilds_fresh_state(self):
        from deeplearning_mpi_tpu.utils.config import execute_training

        trainer, ckpt, args, factory, calls = self._make(fail_times=1, latest=None)
        # Patch out the restart delay to keep the test fast.
        import deeplearning_mpi_tpu.resilience.supervisor as sup
        from unittest import mock

        with mock.patch.object(sup.time, "sleep"):
            out = execute_training(
                trainer, ckpt, args, None, None, 0, state_factory=factory
            )
        assert out == "done"
        # crash before any checkpoint: a FRESH state must be built (the old
        # one's buffers were donated), never the deleted one reused
        assert calls["factory"] == 1
        assert trainer.state == "fresh"
        assert calls["placed"] == 1

    def test_postcheckpoint_crash_restores_latest(self):
        import deeplearning_mpi_tpu.resilience.supervisor as sup
        from unittest import mock

        from deeplearning_mpi_tpu.utils.config import execute_training

        trainer, ckpt, args, factory, calls = self._make(fail_times=1, latest=3)
        with mock.patch.object(sup.time, "sleep"):
            out = execute_training(
                trainer, ckpt, args, None, None, 0, state_factory=factory
            )
        assert out == "done"
        assert calls["restore"] == 1
        assert trainer.state == "restored"


class TestMetricsRegistry:
    def test_counter_gauge_histogram_semantics(self):
        from deeplearning_mpi_tpu.telemetry import MetricsRegistry

        reg = MetricsRegistry()
        c = reg.counter("tokens")
        c.inc()
        c.inc(4.0)
        assert c.value == 5.0
        assert reg.counter("tokens") is c  # get-or-create
        with pytest.raises(ValueError):
            c.inc(-1.0)
        reg.gauge("mfu").set(0.41)
        assert reg.gauge("mfu").value == 0.41
        h = reg.histogram("step_ms")
        for v in (5.0, 1.0, 3.0, 2.0, 4.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 5 and s["mean"] == 3.0
        assert s["p50"] == 3.0 and s["max"] == 5.0
        snap = reg.snapshot()
        assert snap["tokens"] == 5.0 and snap["step_ms_p50"] == 3.0

    def test_emit_canonical_record_shape(self):
        from deeplearning_mpi_tpu.telemetry import InMemorySink, MetricsRegistry

        sink = InMemorySink()
        reg = MetricsRegistry([sink])
        reg.emit("epoch", {"loss": jnp.asarray(1.5), "nan": float("nan"),
                           "note": "x"})
        (rec,) = sink.records
        assert rec["kind"] == "epoch" and isinstance(rec["ts"], float)
        assert rec["loss"] == 1.5 and isinstance(rec["loss"], float)
        assert rec["nan"] is None  # non-finite -> null, JSON-safe
        assert rec["note"] == "x"

    def test_record_step_buffers_without_fetch_then_one_flush(self):
        from deeplearning_mpi_tpu.telemetry import InMemorySink, MetricsRegistry

        sink = InMemorySink()
        reg = MetricsRegistry([sink])
        for step in range(3):
            reg.record_step(step, {"loss": jnp.asarray(float(step))})
        assert sink.records == []  # nothing emitted until the flush
        out = reg.flush_steps(extra={"epoch": 7})
        assert [r["step"] for r in out] == [0, 1, 2]
        assert all(r["kind"] == "step" and r["epoch"] == 7 for r in sink.records)
        assert sink.records[2]["loss"] == 2.0
        assert reg.flush_steps() == []  # buffer drained

    def test_broken_sink_never_raises_into_the_loop(self):
        from deeplearning_mpi_tpu.telemetry import InMemorySink, MetricsRegistry

        class Broken:
            def write(self, record):
                raise RuntimeError("sink died")

            def close(self):
                raise RuntimeError("close died")

        good = InMemorySink()
        reg = MetricsRegistry([Broken(), good])
        reg.emit("step", {"loss": 1.0})  # must not raise
        reg.close()  # must not raise
        assert len(good.records) == 1


class TestJsonlSink:
    def test_round_trip(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry import JsonlSink, MetricsRegistry

        path = tmp_path / "sub" / "metrics.jsonl"  # parent dirs auto-created
        reg = MetricsRegistry([JsonlSink(path)])
        reg.emit("epoch", {"epoch": 0, "loss": 2.5})
        reg.record_step(0, {"loss": jnp.asarray(2.25)})
        reg.close()  # close() drains the pending step buffer
        records = [json.loads(line) for line in path.read_text().splitlines()]
        assert [r["kind"] for r in records] == ["epoch", "step"]
        assert records[0]["loss"] == 2.5 and records[1]["loss"] == 2.25

    def test_report_tool_renders_required_columns(self, tmp_path):
        """tools/metrics_report.py renders a registry-written JSONL with the
        acceptance columns non-null."""
        import pathlib
        import sys

        sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))
        try:
            import metrics_report
        finally:
            sys.path.pop(0)
        from deeplearning_mpi_tpu.telemetry import JsonlSink, MetricsRegistry

        path = tmp_path / "metrics.jsonl"
        reg = MetricsRegistry([JsonlSink(path)])
        reg.record_step(0, {"loss": 2.0, "finite": 1.0})
        reg.flush_steps(extra={"epoch": 0, "comm_bytes": 1e6})
        reg.emit("epoch", {"epoch": 0, "loss": 2.0, "images_per_s": 100.0,
                           "step_ms_p50": 10.0, "step_ms_p95": 12.0,
                           "mfu": 0.3, "comm_bytes_per_step": 1e6})
        reg.close()
        report = metrics_report.summarize(metrics_report.load_records(path))
        for needle in ("images/s", "p50", "p95", "MFU", "collective bytes"):
            assert needle in report


class TestFlopsAndMfu:
    def test_transformer_flops_match_hand_computation(self):
        """Tiny dense config, fwd FLOPs recomputed by hand term by term."""
        from deeplearning_mpi_tpu.models import TransformerConfig
        from deeplearning_mpi_tpu.telemetry.flops import (
            transformer_fwd_flops,
            transformer_train_flops,
        )

        cfg = TransformerConfig(
            vocab_size=256, num_layers=2, num_heads=4, head_dim=8,
            d_model=32, d_ff=64,
        )
        batch, seq = 2, 16
        d, h, dh, ff = 32, 4, 8, 64
        per_token = (
            2 * d * (h * dh) * 2      # q + out projections
            + 2 * d * (h * dh) * 2    # k + v (no GQA: kv heads == heads)
            + 4 * (seq / 2) * h * dh  # scores + values at S/2 visible
            + 6 * d * ff              # SwiGLU gate/up/down
        )
        expected = batch * seq * (2 * per_token + 2 * d * 256)
        assert transformer_fwd_flops(cfg, batch, seq) == pytest.approx(expected)
        assert transformer_train_flops(cfg, batch, seq) == pytest.approx(
            3 * expected
        )

    def test_mfu_arithmetic(self):
        from deeplearning_mpi_tpu.telemetry.flops import mfu

        # 1e9 FLOPs in 0.5 s on 1 device with 200e9 peak -> 1% exactly.
        assert mfu(1e9, 0.5, n_devices=1, peak_flops_per_device=200e9) == (
            pytest.approx(0.01)
        )
        assert mfu(0.0, 0.5, n_devices=1, peak_flops_per_device=1.0) is None
        assert mfu(1e9, 0.0, n_devices=1, peak_flops_per_device=1.0) is None

    def test_peak_flops_env_override(self, monkeypatch):
        from deeplearning_mpi_tpu.telemetry import flops

        monkeypatch.setenv("DMT_PEAK_FLOPS", "123e9")
        assert flops.device_peak_flops() == 123e9

    def test_remat_flops_pinned(self):
        """Pin the remat-aware per-step FLOP accounting to exact literals
        (same tiny config as test_transformer_flops_match_hand_computation,
        batch 2 x seq 16). 'full' re-runs every block forward in the
        backward pass — one extra forward MINUS the head (the loss head is
        outside the remat'd blocks); 'dots' only saves matmul outputs, so
        its recompute is ~free and counted as 0; issued = train + recompute.
        A change to any of these numbers is a change to what mfu_issued and
        mfu_gap report and must be deliberate."""
        from deeplearning_mpi_tpu.models import TransformerConfig
        from deeplearning_mpi_tpu.telemetry.flops import (
            transformer_issued_flops,
            transformer_remat_flops,
            transformer_train_flops,
        )

        cfg = TransformerConfig(
            vocab_size=256, num_layers=2, num_heads=4, head_dim=8,
            d_model=32, d_ff=64,
        )
        batch, seq = 2, 16
        assert transformer_train_flops(cfg, batch, seq) == 5701632.0
        assert transformer_remat_flops(cfg, batch, seq, remat="none") == 0.0
        assert transformer_remat_flops(cfg, batch, seq, remat="dots") == 0.0
        assert transformer_remat_flops(cfg, batch, seq, remat="full") == 1376256.0
        # bool spellings map to the same policies as the model flag.
        assert transformer_remat_flops(cfg, batch, seq, remat=True) == 1376256.0
        assert transformer_remat_flops(cfg, batch, seq, remat=False) == 0.0
        assert transformer_issued_flops(cfg, batch, seq, remat="none") == 5701632.0
        assert transformer_issued_flops(cfg, batch, seq, remat="full") == 7077888.0
        with pytest.raises(ValueError, match="remat"):
            transformer_remat_flops(cfg, batch, seq, remat="sometimes")

    def test_overlap_fraction_roofline(self):
        from deeplearning_mpi_tpu.telemetry.flops import overlap_fraction

        # Compute-bound: compute_s = 2e9/(2*1e12) = 1 ms dwarfs comm_s =
        # (1e6/2)/1e10 = 50 us -> everything hideable, capped at 1.0.
        assert overlap_fraction(
            1e6, 2e9, n_devices=2, peak_flops_per_device=1e12,
            link_bandwidth_per_device=1e10,
        ) == 1.0
        # Comm-bound: comm_s = 50 ms vs compute_s = 1 ms -> 2% hideable.
        assert overlap_fraction(
            1e9, 2e9, n_devices=2, peak_flops_per_device=1e12,
            link_bandwidth_per_device=1e10,
        ) == pytest.approx(0.02)
        # No collective bytes: nothing to hide, trivially 1.0.
        assert overlap_fraction(0.0, 2e9, n_devices=2) == 1.0
        # Degenerate inputs: None, not a fake number.
        assert overlap_fraction(1e6, 0.0) is None
        assert overlap_fraction(None, 2e9) is None
        assert overlap_fraction(-1.0, 2e9) is None

    def test_link_bandwidth_env_override(self, monkeypatch):
        from deeplearning_mpi_tpu.telemetry import flops

        monkeypatch.setenv("DMT_LINK_BANDWIDTH", "42e9")
        assert flops.device_link_bandwidth() == 42e9
        monkeypatch.delenv("DMT_LINK_BANDWIDTH")
        # CPU test devices fall through the TPU table to the nominal figure.
        assert flops.device_link_bandwidth() == (
            flops.CPU_NOMINAL_LINK_BANDWIDTH
        )


class TestCommsAccounting:
    def test_collective_byte_formulas(self):
        from deeplearning_mpi_tpu.telemetry import comms

        B = 1000.0
        assert comms.allreduce_bytes(B, 4) == pytest.approx(2 * 3 / 4 * B)
        assert comms.reduce_scatter_bytes(B, 4) == pytest.approx(3 / 4 * B)
        assert comms.all_gather_bytes(B, 4) == pytest.approx(3 / 4 * B)
        assert comms.all_to_all_bytes(B, 4) == pytest.approx(3 / 4 * B)
        assert comms.ppermute_bytes(B, 4) == B
        # Degenerate single-device axis: everything is free.
        for fn in (comms.allreduce_bytes, comms.reduce_scatter_bytes,
                   comms.all_gather_bytes, comms.all_to_all_bytes,
                   comms.ppermute_bytes):
            assert fn(B, 1) == 0.0

    def test_dp_grad_allreduce_and_zero_equivalence(self):
        from deeplearning_mpi_tpu.telemetry import comms

        n_params, dp = 1_000_000, 8
        plain = comms.dp_grad_allreduce_bytes(n_params, dp)
        zero = comms.dp_grad_allreduce_bytes(n_params, dp, zero=True)
        # ZeRO-1's RS+AG moves the same wire volume as the all-reduce.
        assert plain == pytest.approx(zero)
        assert plain == pytest.approx(2 * 7 / 8 * n_params * 4)

    def test_param_count_never_fetches(self):
        from deeplearning_mpi_tpu.telemetry import comms

        params = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
        assert comms.param_count(params) == 40


class TestTraceAnnotations:
    def test_annotations_do_not_change_train_step_outputs(self):
        """Annotated regions are semantics-free: the same train step on the
        same batch yields bit-identical loss and params with tracing
        enabled vs disabled (CPU mesh; exercises trainer/train_step and the
        model-internal scopes)."""
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
        from deeplearning_mpi_tpu.telemetry import trace
        from deeplearning_mpi_tpu.train import create_train_state
        from deeplearning_mpi_tpu.train.trainer import (
            build_optimizer,
            make_train_step,
        )

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)

        def run_one():
            state = create_train_state(
                model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
            )
            batch = {
                "tokens": jnp.asarray(
                    np.random.default_rng(3).integers(0, 256, (4, 16)),
                    jnp.int32,
                )
            }
            new_state, metrics = make_train_step("lm", donate=False)(state, batch)
            return float(metrics["loss"]), jax.tree.leaves(new_state.params)

        old = trace.set_enabled(True)
        try:
            loss_on, params_on = run_one()
            trace.set_enabled(False)
            loss_off, params_off = run_one()
        finally:
            trace.set_enabled(old)
        assert loss_on == loss_off
        for a, b in zip(params_on, params_off):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_annotate_is_noop_when_disabled(self):
        from deeplearning_mpi_tpu.telemetry import trace

        old = trace.set_enabled(False)
        try:
            with trace.annotate("x"):
                out = jnp.ones(()) + 1.0
        finally:
            trace.set_enabled(old)
        assert float(out) == 2.0

    def test_span_is_the_bare_annotation_and_one_shared_noop_when_disabled(self):
        """``span`` is for host code that runs every step: the
        ``TraceAnnotation`` itself with its labels, no ``named_scope``; with
        annotation disabled, one shared object that takes the same calls."""
        from deeplearning_mpi_tpu.telemetry import trace

        old = trace.set_enabled(True)
        try:
            on = trace.span("serve/x", rows=3)
            assert type(on) is jax.profiler.TraceAnnotation
            with on as sp:
                sp.set_metadata(width=8)
            trace.set_enabled(False)
            off = trace.span("serve/x", rows=3)
            assert not isinstance(off, jax.profiler.TraceAnnotation)
            with off as sp:
                sp.set_metadata(width=8)
            assert sp is off and trace.span("serve/y") is off
        finally:
            trace.set_enabled(old)


class TestTrainerTelemetry:
    def test_trainer_emits_canonical_records_through_registry(self, mesh):
        """Satellite (b): Trainer metric records flow through ONE registry —
        the RunLogger sidecar and any other sink receive identical
        canonical records, per-step scalars included."""
        from deeplearning_mpi_tpu.telemetry import InMemorySink
        from deeplearning_mpi_tpu.train import Trainer, create_train_state
        from deeplearning_mpi_tpu.train.trainer import build_optimizer

        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
        )

        class FakeLoader:
            def epoch(self, epoch):
                rng = np.random.default_rng(epoch)
                for _ in range(3):
                    yield {
                        "tokens": jnp.asarray(
                            rng.integers(0, 256, (8, 16)), jnp.int32
                        )
                    }

        class FakeLogger:
            def __init__(self):
                self.records = []

            def log(self, msg):
                pass

            def log_metrics(self, record):
                self.records.append(dict(record))

        logger = FakeLogger()
        sink = InMemorySink()
        trainer = Trainer(
            state, "lm", mesh, logger=logger, flops_per_step=1e6,
            comm_bytes_per_step=2048.0,
        )
        trainer.metrics.add_sink(sink)
        stats = trainer.run_epoch(FakeLoader(), epoch=0)
        trainer._log_metrics("epoch", stats)
        kinds = [r["kind"] for r in sink.records]
        assert kinds.count("step") == 3 and kinds[-1] == "epoch"
        # LoggerSink fans the SAME records to the RunLogger-style consumer.
        assert logger.records == sink.records
        steps = [r for r in sink.records if r["kind"] == "step"]
        assert [r["step"] for r in steps] == [0, 1, 2]
        assert all(r["epoch"] == 0 and r["comm_bytes"] == 2048.0 for r in steps)
        epoch_rec = sink.records[-1]
        assert epoch_rec["mfu"] is not None and epoch_rec["mfu"] > 0
        assert epoch_rec["comm_bytes_per_step"] == 2048.0
        assert "ts" in epoch_rec

    def test_trainer_emits_mfu_gap_and_overlap_fraction(self, mesh):
        """With issued FLOPs configured, the epoch stats must carry the
        remat-aware companions: mfu_issued (recompute priced in), their
        difference mfu_gap, and the roofline overlap_fraction estimate —
        the columns tools/metrics_report.py renders."""
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
        from deeplearning_mpi_tpu.train import Trainer, create_train_state
        from deeplearning_mpi_tpu.train.trainer import build_optimizer

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
        )

        class FakeLoader:
            def epoch(self, epoch):
                rng = np.random.default_rng(epoch)
                for _ in range(2):
                    yield {
                        "tokens": jnp.asarray(
                            rng.integers(0, 256, (8, 16)), jnp.int32
                        )
                    }

        trainer = Trainer(
            state, "lm", mesh, flops_per_step=1e6,
            issued_flops_per_step=1.3e6, comm_bytes_per_step=2048.0,
        )
        stats = trainer.run_epoch(FakeLoader(), epoch=0)
        assert stats["mfu"] > 0
        assert stats["mfu_issued"] == pytest.approx(1.3 * stats["mfu"])
        assert stats["mfu_gap"] == pytest.approx(
            stats["mfu_issued"] - stats["mfu"]
        )
        assert 0.0 < stats["overlap_fraction"] <= 1.0
        # Without issued FLOPs, none of the companions appear — no fake 0s.
        plain = Trainer(state, "lm", mesh, flops_per_step=1e6)
        stats2 = plain.run_epoch(FakeLoader(), epoch=0)
        assert "mfu_issued" not in stats2 and "mfu_gap" not in stats2
        assert "overlap_fraction" not in stats2

    def test_metrics_every_thins_step_records(self, mesh):
        from deeplearning_mpi_tpu.telemetry import InMemorySink
        from deeplearning_mpi_tpu.train import Trainer, create_train_state
        from deeplearning_mpi_tpu.train.trainer import build_optimizer

        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
        )

        class FakeLoader:
            def epoch(self, epoch):
                rng = np.random.default_rng(epoch)
                for _ in range(4):
                    yield {
                        "tokens": jnp.asarray(
                            rng.integers(0, 256, (8, 16)), jnp.int32
                        )
                    }

        sink = InMemorySink()
        trainer = Trainer(state, "lm", mesh, metrics_every=2, time_steps=False)
        trainer.metrics.add_sink(sink)
        trainer.run_epoch(FakeLoader(), epoch=0)
        steps = [r["step"] for r in sink.records if r["kind"] == "step"]
        assert steps == [0, 2]


class TestSpanTracing:
    """telemetry/spans.py: the span model, the per-process recorder, the
    flight ring, and the JSONL readers — all under fake clocks."""

    @staticmethod
    def _clock(start=0.0):
        t = [start]

        def advance(dt):
            t[0] += dt

        return (lambda: t[0]), advance

    def test_span_tree_nesting_with_fake_clock(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import (
            SpanRecorder,
            load_trace_file,
            span_tree,
        )

        clock, advance = self._clock(100.0)
        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t",
                           clock=clock, epoch_clock=lambda: 1e9)
        root = rec.begin("request", trace="r1", rid=1)
        advance(0.25)
        child = rec.begin("prefill", trace="r1", parent=root.sid)
        advance(0.5)
        rec.end(child)
        advance(0.25)
        rec.end(root)
        rec.close()

        meta, records = load_trace_file(rec.path)
        assert meta["proc"] == "t" and meta["pid"] == rec.pid
        spans = [r for r in records if r["kind"] == "span"]
        # end() writes on close, so the CHILD hits disk first — the tree
        # readers must not rely on parents preceding children.
        assert [s["name"] for s in spans] == ["prefill", "request"]
        by_sid, children, orphans = span_tree(spans)
        assert not orphans
        assert [c["name"] for c in children[root.sid]] == ["prefill"]
        assert by_sid[child.sid]["t1"] - by_sid[child.sid]["t0"] == 0.5
        assert by_sid[root.sid]["t1"] - by_sid[root.sid]["t0"] == 1.0
        assert by_sid[root.sid]["labels"] == {"rid": 1}

    def test_orphan_detection(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import (
            SpanRecorder,
            load_trace_file,
            span_tree,
        )

        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t",
                           clock=lambda: 1.0, epoch_clock=lambda: 2.0)
        rec.record_span("decode", 1.0, 2.0, trace="r7",
                        parent="dead-proc/999:0")
        rec.close()
        _, records = load_trace_file(rec.path)
        _, _, orphans = span_tree(records)
        assert len(orphans) == 1
        assert orphans[0]["parent"] == "dead-proc/999:0"

    def test_flight_ring_evicts_oldest(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import SpanRecorder

        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t", ring=4,
                           clock=lambda: 0.0, epoch_clock=lambda: 0.0,
                           flight_dir=tmp_path / "flight")
        for i in range(10):
            rec.record_span(f"s{i}", float(i), float(i) + 0.5, trace="r0")
        out = rec.dump_flight("unit test")
        rec.close()
        assert out is not None and out.parent == tmp_path / "flight"
        assert "unit-test" in out.name  # reason sanitized for filenames
        payload = json.loads(out.read_text())
        assert payload["spans_total"] == 10
        # Bounded ring: only the 4 most recent records survive to the dump.
        assert [r["name"] for r in payload["ring"]] == [
            "s6", "s7", "s8", "s9",
        ]

    def test_torn_final_line_dropped_on_read(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import (
            SpanRecorder,
            load_trace_file,
        )

        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t",
                           clock=lambda: 5.0, epoch_clock=lambda: 5.0)
        rec.record_span("queue", 1.0, 2.0, trace="r0")
        rec.record_span("decode", 2.0, 3.0, trace="r0")
        rec.close()
        # The single-writer contract's only failure mode: a process dies
        # mid-write and the file ends in half a record, no newline.
        with rec.path.open("a") as f:
            f.write('{"kind": "span", "name": "pref')
        meta, records = load_trace_file(rec.path)
        assert meta is not None
        assert [r["name"] for r in records] == ["queue", "decode"]

    def test_meta_line_carries_clock_offset(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import (
            SpanRecorder,
            load_trace_file,
        )

        # Wall clock 1000, monotonic 400: the offset that places this
        # process's monotonic stamps on the wall-clock timeline is 600.
        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t",
                           clock=lambda: 400.0, epoch_clock=lambda: 1000.0)
        rec.close()
        assert rec.mono_offset == 600.0
        meta, _ = load_trace_file(rec.path)
        assert meta["mono_offset"] == 600.0
        assert meta["ts"] == 1000.0

    def test_skewed_monotonic_clocks_merge_onto_one_timeline(self, tmp_path):
        """Satellite regression: two workers whose monotonic epochs differ
        wildly (different boots) but whose wall clocks agree must merge
        into ONE consistent timeline — each file's own mono_offset does
        the alignment, applied by tools/trace_report.merge_traces."""
        import importlib.util

        from deeplearning_mpi_tpu.telemetry.spans import SpanRecorder

        spec = importlib.util.spec_from_file_location(
            "trace_report",
            Path(__file__).resolve().parent.parent / "tools"
            / "trace_report.py",
        )
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)

        wall = 1.75e9
        a = SpanRecorder(tmp_path / "trace_a.jsonl", proc="a",
                         clock=lambda: 10.0, epoch_clock=lambda: wall)
        b = SpanRecorder(tmp_path / "trace_b.jsonl", proc="b",
                         clock=lambda: 9010.0, epoch_clock=lambda: wall)
        # The same wall instant, expressed in each process's coordinates:
        # a's monotonic reads 10.0 where b's reads 9010.0.
        a.record_span("request", 10.0, 10.5, trace="r0")
        b.record_span("stream", 9010.5, 9010.6, trace="r0")
        a.close()
        b.close()
        _, merged = tr.merge_traces(sorted(tmp_path.glob("trace_*.jsonl")))
        req = next(s for s in merged if s["name"] == "request")
        stream = next(s for s in merged if s["name"] == "stream")
        assert req["t0"] == pytest.approx(wall, abs=1e-6)
        assert stream["t0"] == pytest.approx(req["t1"], abs=1e-6)

    def test_failed_write_degrades_to_dropped_count(self, tmp_path):
        """Recording must never raise into the serving/training hot path:
        a dead file degrades to span_dropped_total, ring still fed."""
        from deeplearning_mpi_tpu.telemetry.spans import SpanRecorder

        rec = SpanRecorder(tmp_path / "trace_t.jsonl", proc="t",
                           clock=lambda: 0.0, epoch_clock=lambda: 0.0)
        rec._f.close()  # simulate the fd dying under the recorder
        span = rec.record_span("decode", 0.0, 1.0, trace="r0")  # no raise
        assert span.duration == 1.0
        assert rec.dropped_total == 1
        assert rec.spans_total == 1
        assert any(r.get("name") == "decode" for r in rec._ring)
        rec.close()

    def test_tracing_off_allocates_nothing(self, tmp_path):
        """Costless-off (the DMT_SANITIZE pattern): with no trace dir the
        hot-path hook is one pointer test — zero allocations, zero files.
        This is the guard exactly as serving/engine.py and
        train/trainer.py write it."""
        import gc
        import sys as _sys

        tracer = None

        def measure(body) -> int:
            gc.collect()
            before = _sys.getallocatedblocks()
            body()
            return _sys.getallocatedblocks() - before

        def baseline():
            for _ in range(10_000):
                pass

        def guarded():
            for _ in range(10_000):
                if tracer is not None:  # the hot-path guard under test
                    tracer.event("engine_step", step=0)

        # The frame machinery itself costs a block or two; the guarded
        # loop must cost no more than the empty loop (min over trials
        # irons out interpreter noise — a REAL per-call allocation would
        # show up ~10k strong in every trial).
        base = min(measure(baseline) for _ in range(5))
        guard = min(measure(guarded) for _ in range(5))
        assert guard <= base, (
            f"tracing-off guard allocated: {guard} blocks vs "
            f"baseline {base}"
        )
        assert list(tmp_path.glob("trace_*.jsonl")) == []

    def test_dump_all_covers_every_live_recorder(self, tmp_path):
        from deeplearning_mpi_tpu.telemetry.spans import (
            SpanRecorder,
            dump_all,
        )

        a = SpanRecorder(tmp_path / "trace_a.jsonl", proc="a",
                         clock=lambda: 0.0, epoch_clock=lambda: 0.0,
                         flight_dir=tmp_path / "flight")
        b = SpanRecorder(tmp_path / "trace_b.jsonl", proc="b",
                         clock=lambda: 0.0, epoch_clock=lambda: 0.0,
                         flight_dir=tmp_path / "flight")
        try:
            a.record_span("x", 0.0, 1.0)
            paths = dump_all("sanitizer-test")
            ours = [p for p in paths
                    if Path(p).parent == tmp_path / "flight"]
            assert len(ours) == 2
            procs = {json.loads(Path(p).read_text())["proc"] for p in ours}
            assert procs == {"a", "b"}
        finally:
            a.close()
            b.close()
        # Closed recorders leave the registry: a later dump skips them.
        assert not [p for p in dump_all("after-close")
                    if Path(p).parent == tmp_path / "flight"]
