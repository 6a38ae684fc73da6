"""Compilation service: AOT warmup, tuning DB, cache management, donation.

Covers the ``compiler/`` subsystem end to end on the virtual-CPU harness:

- TuningDB round-trip / corruption / exact-key lookup semantics;
- autotuned candidates match the default kernels numerically (the DB can
  make kernels faster, never wrong);
- the buffer-donation veto policy matrix (moved here from
  ``runtime/compat.py`` — the regression test for the XLA:CPU
  deserialized-executable heap corruption);
- cold-vs-warm AOT compile classification against a persistent cache
  (miss writes an entry, a second identical program deserializes);
- CompileCache LRU eviction and digest-manifest quarantine (fabricated
  entries — no real compiles needed);
- a warmed ServingEngine performs ZERO compiles on its first request
  (the ``serve_compile_total`` trace counter), and ``Trainer.warmup``
  swaps in a working AOT step.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_mpi_tpu.compiler import aot, autotune
from deeplearning_mpi_tpu.compiler import cache as ccache
from deeplearning_mpi_tpu.telemetry import MetricsRegistry

F32 = jnp.float32


# -- tuning DB ----------------------------------------------------------------

class TestTuningDB:
    def test_round_trip(self, tmp_path):
        db = autotune.TuningDB(tmp_path / "t.json")
        db.record("flash_attention", (1, 64, 2, 16), F32,
                  {"block_q": 32, "block_k": 64}, backend="cpu",
                  best_seconds=0.01)
        db.record("flash_decode", (2, 64, 2, 16), F32,
                  {"schedule": "einsum", "block": None}, backend="cpu")
        db.save()
        back = autotune.TuningDB.load(tmp_path / "t.json")
        assert len(back) == 2
        assert back.lookup("flash_attention", (1, 64, 2, 16), F32,
                           backend="cpu") == {"block_q": 32, "block_k": 64}

    def test_corrupt_file_loads_empty_and_saves(self, tmp_path):
        p = tmp_path / "t.json"
        p.write_text("{not json")
        db = autotune.TuningDB.load(p)
        assert len(db) == 0
        db.record("flash_attention", (1, 8, 1, 8), F32,
                  {"block_q": 8, "block_k": 8}, backend="cpu")
        db.save()  # path survived the corrupt load
        assert len(autotune.TuningDB.load(p)) == 1

    def test_version_mismatch_ignored(self, tmp_path):
        p = tmp_path / "t.json"
        p.write_text('{"version": 99, "entries": {"x": {}}}')
        assert len(autotune.TuningDB.load(p)) == 0

    def test_lookup_is_exact_key_only(self):
        db = autotune.TuningDB()
        db.record("flash_attention", (1, 64, 2, 16), F32,
                  {"block_q": 32, "block_k": 64}, backend="cpu")
        assert db.lookup("flash_attention", (1, 128, 2, 16), F32,
                         backend="cpu") is None
        assert db.lookup("flash_attention", (1, 64, 2, 16), F32,
                         backend="tpu") is None
        assert db.lookup("flash_attention", (1, 64, 2, 16), jnp.bfloat16,
                         backend="cpu") is None

    def test_env_var_default_db(self, tmp_path, monkeypatch):
        db = autotune.TuningDB(tmp_path / "env.json")
        db.record("flash_attention", (1, 64, 2, 16), F32,
                  {"block_q": 16, "block_k": 16})
        db.save()
        monkeypatch.setenv(autotune.ENV_DB, str(tmp_path / "env.json"))
        autotune.set_default_db(None)  # re-arm the env fallback
        try:
            loaded = autotune.default_db()
            assert loaded is not None and len(loaded) == 1
        finally:
            monkeypatch.delenv(autotune.ENV_DB)
            autotune.set_default_db(None)


# -- autotuner ----------------------------------------------------------------

class TestAutotune:
    SHAPE = (1, 64, 2, 16)

    def test_attention_candidates_legal(self):
        pairs = autotune.attention_candidates(64, candidates=(16, 32, 64, 128))
        assert pairs, "64-seq shape must admit candidates"
        for bq, bk in pairs:
            assert bq <= 64 and bk <= 64
            assert 64 % bq == 0 and 64 % bk == 0

    def test_tuned_attention_matches_oracle(self, tmp_path):
        from deeplearning_mpi_tpu.ops.attention import dense_attention
        from deeplearning_mpi_tpu.ops.pallas import flash_attention

        db = autotune.TuningDB(tmp_path / "t.json")
        params = autotune.tune_flash_attention(
            self.SHAPE, db=db, candidates=(32, 64), repeats=1,
        )
        assert set(params) == {"block_q", "block_k"}
        kq, kk, kv = jax.random.split(jax.random.key(7), 3)
        q = jax.random.normal(kq, self.SHAPE)
        k = jax.random.normal(kk, self.SHAPE)
        v = jax.random.normal(kv, self.SHAPE)
        tuned = flash_attention(
            q, k, v, block_q=params["block_q"], block_k=params["block_k"]
        )
        np.testing.assert_allclose(
            np.asarray(tuned), np.asarray(dense_attention(q, k, v)),
            rtol=2e-5, atol=2e-5,
        )

    def test_tune_decode_schedule_and_lookup(self, tmp_path):
        db = autotune.TuningDB(tmp_path / "t.json")
        params = autotune.tune_flash_decode(
            (2, 64, 2, 16), db=db, blocks=(16, 32), repeats=1,
        )
        assert params["schedule"] in ("kernel", "einsum")
        autotune.set_default_db(db)
        try:
            got = autotune.tuned_decode_schedule((2, 64, 2, 16), F32)
            assert got is not None and got["schedule"] == params["schedule"]
            # einsum winner must never hand a block to the kernel path.
            if got["schedule"] == "einsum":
                assert got["block"] is None
        finally:
            autotune.set_default_db(None)

    def test_resolve_blocks_db_override(self):
        from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
            DEFAULT_BLOCK_K,
            DEFAULT_BLOCK_Q,
            resolve_blocks,
        )

        db = autotune.TuningDB()
        db.record("flash_attention", self.SHAPE, F32,
                  {"block_q": 16, "block_k": 32})
        autotune.set_default_db(db)
        try:
            assert resolve_blocks(None, None, self.SHAPE, F32) == (16, 32)
            # Explicit kwargs always beat the DB, per-axis.
            assert resolve_blocks(8, None, self.SHAPE, F32) == (8, 32)
            assert resolve_blocks(None, 8, self.SHAPE, F32) == (16, 8)
            # Untuned shape: module defaults.
            assert resolve_blocks(None, None, (1, 128, 2, 16), F32) == (
                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
            )
        finally:
            autotune.set_default_db(None)

    def test_broken_default_db_never_raises(self):
        from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
            resolve_blocks,
        )

        class Broken:
            def lookup(self, *a, **k):
                raise RuntimeError("boom")

        autotune._default_db = Broken()  # simulate a poisoned DB object
        try:
            assert autotune.tuned_attention_blocks(self.SHAPE, F32) is None
            assert resolve_blocks(None, None, self.SHAPE, F32)
        finally:
            autotune.set_default_db(None)


# -- spec-k (`spec_k|...` key space) ------------------------------------------

class TestSpecKTuning:
    def test_expected_tokens_per_step(self):
        # a=0: only the bonus token ever lands. a=1: all k + bonus.
        assert autotune.expected_tokens_per_step(0.0, 4) == 1.0
        assert autotune.expected_tokens_per_step(1.0, 4) == 5.0
        # Truncated geometric series: a=0.5, k=2 -> 1 + .5 + .25 = 1.75.
        assert autotune.expected_tokens_per_step(0.5, 2) == 1.75
        # Out-of-range rates clamp instead of exploding.
        assert autotune.expected_tokens_per_step(2.0, 3) == 4.0

    def test_spec_k_consult_never_raises(self):
        from deeplearning_mpi_tpu.models import TransformerConfig

        class Broken:
            def lookup_key(self, *a, **k):
                raise RuntimeError("boom")

        autotune._default_db = Broken()
        try:
            assert autotune.tuned_spec_k(TransformerConfig.tiny(), 1, F32) is None
        finally:
            autotune.set_default_db(None)

    def test_tune_spec_k_records_winner(self, tmp_path):
        from deeplearning_mpi_tpu.models import TransformerConfig

        db = autotune.TuningDB(tmp_path / "s.json")
        won = autotune.tune_spec_k(
            draft_layers=1, db=db, candidates=(0, 2),
            num_requests=2, max_new_tokens=8,
        )
        assert isinstance(won["spec_k"], int) and won["spec_k"] in (0, 2)
        autotune.set_default_db(db)
        try:
            got = autotune.tuned_spec_k(TransformerConfig.tiny(), 1, F32)
            assert got is not None and got["spec_k"] == won["spec_k"]
            # A different draft depth is a different key: clean miss.
            assert autotune.tuned_spec_k(TransformerConfig.tiny(), 3, F32) is None
        finally:
            autotune.set_default_db(None)


# -- whole-step schedule tuner (`step|...` key space) -------------------------

class TestStepTuning:
    def test_key_canonical_across_mesh_forms(self):
        key = autotune.step_tuning_key(
            "lm", (8, 16), {"data": 2}, F32, backend="cpu"
        )
        assert key == "step|lm|8x16|data2|float32|cpu"
        # Size-1 axes carry no sharding: a MeshSpec that materializes every
        # axis and a hand-built data-only Mesh must agree on the key.
        assert autotune.step_tuning_key(
            "lm", (8, 16), {"data": 2, "pipe": 1, "model": 1}, F32,
            backend="cpu",
        ) == key
        assert autotune.step_tuning_key(
            "lm", (8, 16), "data2", F32, backend="cpu"
        ) == key
        # All-size-1 mesh canonicalizes to "1", not an empty field.
        assert autotune.step_tuning_key(
            "lm", (8, 16), {"data": 1}, F32, backend="cpu"
        ) == "step|lm|8x16|1|float32|cpu"

    def test_step_candidates_space(self):
        flat = autotune.step_candidates(1)
        assert flat and all(not c["overlap"] for c in flat)
        dp = autotune.step_candidates(2)
        assert any(c["overlap"] for c in dp)
        assert {c["remat"] for c in dp} == set(autotune.STEP_REMAT_CANDIDATES)
        # Overlap doubles the space; nothing else changes.
        assert len(dp) == 2 * len(flat)

    def test_tune_persists_verified_winner_and_round_trips(self, tmp_path):
        from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh

        db = autotune.TuningDB(tmp_path / "t.json")
        params = autotune.tune_step_schedule(
            "lm", batch_size=8, seq_len=16, db=db,
            candidates=[
                {"remat": "none", "grad_accum": 1, "donate": False,
                 "overlap": False},
                {"remat": "dots", "grad_accum": 2, "donate": False,
                 "overlap": False},
                # 8 % 3 != 0 — must be recorded rejected, not attempted.
                {"remat": "none", "grad_accum": 3, "donate": False,
                 "overlap": False},
            ],
            steps=3, repeats=1,
        )
        assert set(params) == {"remat", "grad_accum", "donate", "overlap"}
        db.save()
        text = (tmp_path / "t.json").read_text()
        assert '"rejected": "unsupported"' in text  # the ga=3 candidate
        # Round-trip through a freshly loaded DB, consulting with the same
        # (default) mesh the tuner keyed on.
        back = autotune.TuningDB.load(tmp_path / "t.json")
        mesh = create_mesh(MeshSpec(data=len(jax.devices())))
        got = autotune.tuned_step_schedule("lm", (8, 16), mesh, F32, db=back)
        assert got == params
        # The consult is logged for bench provenance (key + recorded median).
        assert back.consulted and back.consulted[0]["params"] == params
        assert back.consulted[0]["key"].startswith("step|lm|8x16|")
        assert back.consulted[0]["best_seconds"] > 0

    def test_tuned_step_schedule_never_raises(self, tmp_path):
        mesh = {"data": 2}
        # Empty DB and corrupt-file DB: miss, not exception.
        assert autotune.tuned_step_schedule(
            "lm", (8, 16), mesh, F32, db=autotune.TuningDB()
        ) is None
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        assert autotune.tuned_step_schedule(
            "lm", (8, 16), mesh, F32, db=autotune.TuningDB.load(p)
        ) is None

        class Broken:
            def lookup_key(self, *a, **k):
                raise RuntimeError("boom")

        # A poisoned DB object — passed explicitly or installed as the
        # process default — degrades to None, never into the training run.
        assert autotune.tuned_step_schedule(
            "lm", (8, 16), mesh, F32, db=Broken()
        ) is None
        autotune._default_db = Broken()
        try:
            assert autotune.tuned_step_schedule("lm", (8, 16), mesh, F32) is None
        finally:
            autotune.set_default_db(None)

    def test_non_lm_model_rejected(self, tmp_path):
        import pytest

        with pytest.raises(ValueError, match="lm"):
            autotune.tune_step_schedule(
                "classification", batch_size=8, seq_len=16,
                db=autotune.TuningDB(tmp_path / "t.json"), steps=1, repeats=1,
            )


# -- donation veto policy (regression: XLA:CPU heap corruption) ---------------

class TestDonationPolicy:
    def test_policy_matrix(self):
        assert ccache.donation_safe("cpu", True) is False
        assert ccache.donation_safe("cpu", False) is True
        assert ccache.donation_safe("tpu", True) is True
        assert ccache.donation_safe("gpu", True) is True

    def test_live_config_vetoed_under_test_cache(self):
        # conftest.py enables the persistent cache on CPU — the exact
        # configuration the veto exists for.
        from deeplearning_mpi_tpu.runtime.compat import (
            buffer_donation_supported,
        )

        assert jax.config.jax_compilation_cache_dir
        assert ccache.donation_safe() is False
        assert buffer_donation_supported() is False  # compat shim delegates

    def test_compile_program_strips_donation(self):
        prog = aot.compile_program(
            "donation_probe", lambda x: x * 2.0,
            jnp.ones((4,), F32), donate_argnums=(0,),
        )
        assert prog.donated == ()
        np.testing.assert_allclose(
            np.asarray(prog(jnp.ones((4,), F32))), 2.0 * np.ones((4,))
        )


# -- CompileCache management (fabricated entries; no real compiles) -----------

def _fake_entry(path, name, size, age):
    """One synthetic `jit_*-cache` entry + its `-atime` sibling, `age`
    seconds old in LRU terms."""
    entry = path / f"jit_{name}-cache"
    entry.write_bytes(b"x" * size)
    atime = path / f"jit_{name}-atime"
    atime.write_bytes(b"")
    t = 1_700_000_000 + age
    os.utime(atime, (t, t))
    return entry


class TestCompileCache:
    def test_entries_lru_order_and_stats(self, tmp_path):
        _fake_entry(tmp_path, "b", 10, age=200)
        _fake_entry(tmp_path, "a", 30, age=100)
        cache = ccache.CompileCache(tmp_path)
        names = [e.name for e in cache.entries()]
        assert names == ["jit_a-cache", "jit_b-cache"]  # oldest-used first
        assert cache.size_bytes() == 40
        assert cache.stats()["entries"] == 2

    def test_evict_lru(self, tmp_path):
        registry = MetricsRegistry()
        _fake_entry(tmp_path, "old", 100, age=0)
        _fake_entry(tmp_path, "mid", 100, age=100)
        kept = _fake_entry(tmp_path, "hot", 100, age=200)
        cache = ccache.CompileCache(tmp_path, registry=registry)
        evicted = cache.evict(max_bytes=150)
        assert [e.name for e in evicted] == ["jit_old-cache", "jit_mid-cache"]
        assert kept.exists()
        assert not (tmp_path / "jit_old-cache").exists()
        assert not (tmp_path / "jit_old-atime").exists()  # sibling removed
        assert registry.counter("compile_cache_evicted_total").value == 2
        assert cache.evict(max_bytes=150) == []  # already fits

    def test_quarantine_corrupt_entry(self, tmp_path):
        registry = MetricsRegistry()
        good = _fake_entry(tmp_path, "good", 50, age=0)
        bad = _fake_entry(tmp_path, "bad", 50, age=0)
        cache = ccache.CompileCache(tmp_path, registry=registry)
        cache.write_manifest()
        bad.write_bytes(b"flipped bits")  # corrupt after manifest
        assert cache.verify() == ["jit_bad-cache"]
        assert not bad.exists()
        qdir = tmp_path / ccache.QUARANTINE_DIR
        assert (qdir / "jit_bad-cache").exists()
        assert (qdir / "jit_bad-atime").exists()
        assert good.exists()
        assert registry.counter("compile_cache_quarantined_total").value == 1
        assert cache.verify() == []  # quarantined entry no longer listed

    def test_new_entries_pass_verify(self, tmp_path):
        cache = ccache.CompileCache(tmp_path)
        _fake_entry(tmp_path, "a", 10, age=0)
        cache.write_manifest()
        _fake_entry(tmp_path, "later", 10, age=10)  # post-manifest entry
        assert cache.verify() == []

    def test_disabled_cache_degrades(self, tmp_path):
        cache = ccache.CompileCache(tmp_path / "missing")
        assert not cache.enabled
        assert cache.entries() == []
        assert cache.evict(0) == []
        assert cache.verify() == []
        assert cache.observe_compile("x", 0.1, frozenset()) is None


# -- AOT compile + warmup -----------------------------------------------------

class TestAOT:
    def test_abstractify(self):
        tree = {"a": jnp.ones((2, 3), jnp.bfloat16), "b": np.zeros((4,))}
        out = aot.abstractify(tree)
        assert out["a"] == jax.ShapeDtypeStruct((2, 3), jnp.bfloat16)
        assert out["b"].shape == (4,)

    def test_compile_program_matches_jit(self):
        f = lambda x, y: (x @ y).sum()
        x = jnp.arange(12.0).reshape(3, 4)
        y = jnp.ones((4, 5))
        prog = aot.compile_program("matmul_sum", f, x, y)
        np.testing.assert_allclose(np.asarray(prog(x, y)), np.asarray(f(x, y)))
        assert prog.lower_seconds >= 0 and prog.compile_seconds >= 0

    def test_cold_then_warm_cache_classification(self, tmp_path):
        prev_dir = jax.config.jax_compilation_cache_dir
        prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            ccache.enable(tmp_path / "xla")  # min_compile_time 0: persist all
            x = jnp.arange(8.0)

            reg1 = MetricsRegistry()
            cold = aot.compile_program(
                "probe", jax.jit(lambda x: (x * 3.0 + 1.0).sum()), x,
                cache=ccache.CompileCache(registry=reg1),
            )
            assert cold.cache_hit is False
            assert reg1.counter("compile_cache_miss_total").value == 1

            reg2 = MetricsRegistry()  # fresh jit object, identical program
            warm = aot.compile_program(
                "probe", jax.jit(lambda x: (x * 3.0 + 1.0).sum()), x,
                cache=ccache.CompileCache(registry=reg2),
            )
            assert warm.cache_hit is True
            assert reg2.counter("compile_cache_hit_total").value == 1
            np.testing.assert_allclose(np.asarray(cold(x)), np.asarray(warm(x)))
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev_min
            )
            ccache._reset_backend_cache()  # un-pin the tmp dir

    def test_warm_program_fallback_on_shape_drift(self):
        jitted = jax.jit(lambda x: x * 2.0)
        prog = aot.compile_program("doubler", jitted, jnp.ones((8,), F32))
        warm = aot.WarmProgram(prog, jitted)
        np.testing.assert_allclose(
            np.asarray(warm(jnp.ones((8,), F32))), 2.0 * np.ones((8,))
        )
        assert warm.fallback_calls == 0
        # Unseen aval: the Compiled rejects, the fallback answers.
        np.testing.assert_allclose(
            np.asarray(warm(jnp.ones((4,), F32))), 2.0 * np.ones((4,))
        )
        assert warm.fallback_calls == 1

    def test_warmup_registry_sweep(self):
        registry = MetricsRegistry()
        reg = aot.WarmupRegistry(registry=registry)
        reg.register("f", lambda x: x + 1.0, jnp.zeros((3,), F32))
        reg.register("g", lambda x: x * 2.0, jnp.zeros((3,), F32))
        programs = reg.warm_all()
        assert set(programs) == {"f", "g"}
        np.testing.assert_allclose(
            np.asarray(reg.get("f")(jnp.zeros((3,), F32))), np.ones((3,))
        )


# -- warmed engine / trainer --------------------------------------------------

class TestWarmedEngine:
    def _engine(self, registry):
        from deeplearning_mpi_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )
        from deeplearning_mpi_tpu.serving import EngineConfig, ServingEngine

        cfg = TransformerConfig.tiny()
        params = TransformerLM(config=cfg, dtype=F32).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        return ServingEngine(
            cfg, params,
            EngineConfig(max_slots=2, block_size=8, num_blocks=16,
                         max_blocks_per_seq=4, prefill_chunk=8, max_queue=8),
            dtype=F32, registry=registry,
        )

    @staticmethod
    def _first_request(engine):
        """One 8-token prompt served to its 4 tokens; returns them."""
        from deeplearning_mpi_tpu.serving import RequestState

        req = engine.submit(np.arange(1, 9, dtype=np.int32), 4)
        while not engine.scheduler.idle():
            engine.step()
        assert req.state is RequestState.FINISHED
        return list(req.generated)

    def test_zero_compiles_on_first_request(self):
        registry = MetricsRegistry()
        engine = self._engine(registry)
        engine.warmup()
        # Warmup compiled every table shape the bucket functions can emit,
        # once each (the trace-time tick in decode_step/prefill_chunk): the
        # decode program at its (rows, width) pairs, the prefill chunk at
        # every width — here rows 1 and 2 at widths 2 and 4 and rows 2 at
        # width 1, prefill at (1, 2, 4).
        compiles = registry.counter("serve_compile_total").value
        assert compiles == len(engine._decode_shapes) + len(engine._widths)
        self._first_request(engine)
        # The actual contract: the first request compiled NOTHING.
        assert registry.counter("serve_compile_total").value == compiles
        # Every table shape has its own AOT executable, picked by the
        # table's shape: the chunk (8 tokens, one block) and the decode
        # rows never fell through to the jit behind them.
        assert engine._prefill_fn.fallback_calls == 0
        assert engine._decode_fn.fallback_calls == 0

    def test_a_tuning_db_does_not_reach_the_decode_program(self):
        """With a default DB holding ``flash_decode`` and ``flash_attention``
        winners for the very shapes the engine gathers and prefills, a warmed
        engine compiles nothing and emits the DB-less engine's tokens: no
        serving program consults the DB."""

        def run(registry):
            engine = self._engine(registry)
            engine.warmup()
            compiles = registry.counter("serve_compile_total").value
            tokens = self._first_request(engine)
            assert registry.counter("serve_compile_total").value == compiles
            return engine, tokens

        engine, want = run(MetricsRegistry())
        cfg, e = engine.config, engine.engine
        kv_heads = cfg.num_kv_heads or cfg.num_heads
        db = autotune.TuningDB()
        for rows, width in engine._decode_shapes:
            db.record(
                "flash_decode", (rows, width * e.block_size, kv_heads, cfg.head_dim),
                F32, {"schedule": "kernel", "block": e.block_size},
            )
        for width in engine._widths:
            db.record(
                "flash_attention", (1, width * e.block_size, cfg.num_heads, cfg.head_dim),
                F32, {"block_q": 8, "block_k": 8},
            )
        autotune.set_default_db(db)
        try:
            _, got = run(MetricsRegistry())
        finally:
            autotune.set_default_db(None)
        assert got == want
        assert not db.consulted

    def test_warmed_matches_unwarmed_tokens(self):
        def run(warm):
            engine = self._engine(MetricsRegistry())
            if warm:
                engine.warmup()
            return self._first_request(engine)

        assert run(warm=True) == run(warm=False)


class TestTrainerWarmup:
    def test_trainer_warmup_swaps_working_step(self, mesh):
        import optax

        from deeplearning_mpi_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )
        from deeplearning_mpi_tpu.train import Trainer, create_train_state

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=F32)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32),
            optax.sgd(1e-2),
        )
        trainer = Trainer(state, "lm", mesh)
        batch = {
            "tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, 256, (4, 16)), jnp.int32
            )
        }
        prog = trainer.warmup(batch)
        assert isinstance(trainer.train_step, aot.WarmProgram)
        assert prog.compile_seconds >= 0
        new_state, metrics = trainer.train_step(trainer.state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == int(state.step) + 1
        assert trainer.train_step.fallback_calls == 0
