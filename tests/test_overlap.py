"""Explicit bucketed ZeRO-1 schedule vs the GSPMD path — bit-equality.

The acceptance bar for ``parallel.zero.make_overlapped_train_step``: over
>= 5 optimizer steps on a dp=2 CPU mesh, the overlapped schedule must
produce *bit-identical* optimizer state (and params, and per-step losses)
to the GSPMD ZeRO-1 step, with and without the global-norm clip. Bitwise
claims use untied embeddings and the one-hot embedding gradient
(``TransformerConfig.onehot_embed``) — the two documented association
caveats (see parallel/zero.py's module docstring); tied embeddings and the
EMA of the ZeRO-sharded leaves are covered at allclose.
"""

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
from deeplearning_mpi_tpu.parallel import shard_state
from deeplearning_mpi_tpu.parallel.tensor_parallel import infer_state_sharding
from deeplearning_mpi_tpu.parallel.zero import (
    BUCKET_BYTES,
    OverlapUnsupported,
    make_overlapped_train_step,
    plan_buckets,
    zero1_dim,
)
from deeplearning_mpi_tpu.runtime.mesh import (
    MeshSpec,
    batch_sharding,
    create_mesh,
)
from deeplearning_mpi_tpu.train import create_train_state, make_train_step
from deeplearning_mpi_tpu.train.trainer import build_optimizer

VOCAB = 256


def _mesh(dp=2, **axes):
    n = dp
    for v in axes.values():
        n *= v
    return create_mesh(MeshSpec(data=dp, **axes), devices=jax.devices()[:n])


def _lm_state(*, tied=False, clip=None, ema=False, tx=None, onehot=True):
    cfg = TransformerConfig(
        vocab_size=VOCAB, num_layers=1, num_heads=2, head_dim=32,
        d_model=64, d_ff=256, tied_embeddings=tied, onehot_embed=onehot,
    )
    model = TransformerLM(config=cfg, dtype=jnp.float32)
    tx = tx if tx is not None else build_optimizer("adam", 1e-2, clip_norm=clip)
    return create_train_state(
        model, jax.random.key(0), jnp.zeros((1, 8), jnp.int32), tx, ema=ema
    )


def _batches(mesh, n=5, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tokens = jnp.asarray(rng.integers(0, VOCAB, (batch, seq)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, (batch, seq)), jnp.float32)
        out.append({
            "tokens": jax.device_put(tokens, batch_sharding(mesh, ndim=2)),
            "mask": jax.device_put(mask, batch_sharding(mesh, ndim=2)),
        })
    return out


def _run(step, state, batches):
    losses = []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
    return state, losses


def _assert_tree_bit_equal(a, b, what):
    for (kp, x), (_, y) in zip(
        jtu.tree_flatten_with_path(a)[0], jtu.tree_flatten_with_path(b)[0]
    ):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{what}{jtu.keystr(kp)} not bit-identical",
        )


class TestBitEquality:
    """Overlapped schedule == GSPMD schedule, bit for bit (dp=2, 5 steps)."""

    def _compare(self, *, clip=None, ema=False, n_steps=5):
        mesh = _mesh()
        ema_decay = 0.9 if ema else 0.0
        state_g = shard_state(_lm_state(clip=clip, ema=ema), mesh, zero=True)
        state_o = shard_state(_lm_state(clip=clip, ema=ema), mesh, zero=True)
        step_g = make_train_step(
            "lm", donate=False, ema_decay=ema_decay,
            state_shardings=infer_state_sharding(state_g, mesh, zero=True),
        )
        step_o = make_overlapped_train_step(
            "lm", state_o, mesh, donate=False, clip_norm=clip,
            ema_decay=ema_decay,
        )
        batches = _batches(mesh, n=n_steps)
        state_g, losses_g = _run(step_g, state_g, batches)
        state_o, losses_o = _run(step_o, state_o, batches)
        assert losses_g == losses_o, "per-step losses diverged"
        _assert_tree_bit_equal(state_g.opt_state, state_o.opt_state, "opt_state")
        _assert_tree_bit_equal(state_g.params, state_o.params, "params")
        if ema:
            # d*e + (1-d)*p over bit-equal params: XLA:CPU contracts it to
            # one fma in the GSPMD step's fusion and not after the explicit
            # all_gather, for the ZeRO-sharded leaves only — half an ulp of
            # the product at step 0 (7.5e-9), 8.9e-8 after five (module
            # docstring of parallel/zero.py).
            for a, b in zip(
                jax.tree.leaves(state_g.ema_params),
                jax.tree.leaves(state_o.ema_params),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6, atol=2e-7
                )
        assert int(state_o.step) == n_steps

    def test_bitwise_vs_gspmd_5_steps(self):
        self._compare()

    def test_bitwise_with_clip(self):
        # The pre-clip mirrors optax.clip_by_global_norm's exact form, so
        # even the clipped path lands bit-equal on this mesh.
        self._compare(clip=1.0)

    def test_clip_and_ema_bitwise_state_and_ema_allclose(self):
        self._compare(clip=1.0, ema=True)

    def test_tied_embeddings_allclose(self):
        # Tied embed grads: GSPMD adds two separately all-reduced cotangent
        # contributions; the local backward adds before one reduce. Same
        # value to ~2 ulp — allclose, not bitwise (module docstring).
        mesh = _mesh()
        state_g = shard_state(_lm_state(tied=True), mesh, zero=True)
        state_o = shard_state(_lm_state(tied=True), mesh, zero=True)
        step_g = make_train_step(
            "lm", donate=False,
            state_shardings=infer_state_sharding(state_g, mesh, zero=True),
        )
        step_o = make_overlapped_train_step("lm", state_o, mesh, donate=False)
        batches = _batches(mesh)
        state_g, losses_g = _run(step_g, state_g, batches)
        state_o, losses_o = _run(step_o, state_o, batches)
        np.testing.assert_allclose(losses_g, losses_o, rtol=1e-6)
        for a, b in zip(
            jax.tree.leaves(state_g.params), jax.tree.leaves(state_o.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
            )

    def test_nan_batch_skipped_like_gspmd(self):
        mesh = _mesh()
        state_o = shard_state(_lm_state(), mesh, zero=True)
        step_o = make_overlapped_train_step("lm", state_o, mesh, donate=False)
        batches = _batches(mesh, n=1)
        before = jax.tree.map(np.asarray, state_o.params)
        poisoned = dict(batches[0])
        poisoned["mask"] = poisoned["mask"] * jnp.nan
        state_o, metrics = step_o(state_o, poisoned)
        assert float(metrics["finite"]) == 0.0
        _assert_tree_bit_equal(before, state_o.params, "params after NaN skip")
        assert int(state_o.step) == 1  # step counter still advances


class TestGradAccum:
    def test_grad_accum_matches_full_batch(self):
        mesh = _mesh()
        state_1 = shard_state(_lm_state(), mesh, zero=True)
        state_k = shard_state(_lm_state(), mesh, zero=True)
        step_1 = make_overlapped_train_step("lm", state_1, mesh, donate=False)
        step_k = make_overlapped_train_step(
            "lm", state_k, mesh, donate=False, grad_accum=2
        )
        batches = _batches(mesh, n=3)
        state_1, losses_1 = _run(step_1, state_1, batches)
        state_k, losses_k = _run(step_k, state_k, batches)
        # Local chunking is algebraically identical to the full-batch masked
        # mean (weights fold exactly); only fp association differs — and
        # Adam's nu-normalization amplifies ulp-level grad differences on
        # near-zero coordinates, hence the looser param tolerance.
        np.testing.assert_allclose(losses_1, losses_k, rtol=1e-6)
        for a, b in zip(
            jax.tree.leaves(state_1.params), jax.tree.leaves(state_k.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3
            )

    def test_nondivisible_batch_names_offender(self):
        mesh = _mesh()
        state = shard_state(_lm_state(), mesh, zero=True)
        step = make_overlapped_train_step(
            "lm", state, mesh, donate=False, grad_accum=4
        )
        [batch] = _batches(mesh, n=1, batch=6)  # local batch 3, accum 4
        with pytest.raises(ValueError, match=r"\(3, 16\).*grad_accum=4"):
            step(state, batch)


class TestUnsupportedFallsBack:
    def test_no_data_parallelism(self):
        mesh = _mesh(dp=1)
        state = _lm_state()
        with pytest.raises(OverlapUnsupported, match="size 1"):
            make_overlapped_train_step("lm", state, mesh)

    def test_non_data_axes(self):
        mesh = _mesh(dp=2, model=2)
        state = _lm_state()
        with pytest.raises(OverlapUnsupported, match="non-data"):
            make_overlapped_train_step("lm", state, mesh)

    def test_aux_weight(self):
        with pytest.raises(OverlapUnsupported, match="aux_weight"):
            make_overlapped_train_step("lm", _lm_state(), _mesh(), aux_weight=0.1)

    def test_loss_chunk(self):
        with pytest.raises(OverlapUnsupported, match="loss_chunk"):
            make_overlapped_train_step("lm", _lm_state(), _mesh(), loss_chunk=8)

    def test_batch_stats(self):
        state = _lm_state().replace(
            batch_stats={"bn": {"mean": jnp.zeros((4,))}}
        )
        with pytest.raises(OverlapUnsupported, match="batch_stats"):
            make_overlapped_train_step("lm", state, _mesh())

    def test_non_mirroring_optimizer_state(self):
        # Factored adafactor moments don't mirror parameter shapes; the
        # build-time eval_shape probe must catch it, not a mid-step error.
        tx = optax.adafactor(
            1e-2, multiply_by_parameter_scale=False, min_dim_size_to_factor=32
        )
        state = _lm_state(tx=tx)
        with pytest.raises(OverlapUnsupported, match="mirror"):
            make_overlapped_train_step("lm", state, _mesh())


class TestBucketPlan:
    def _leaves(self):
        return [
            jnp.zeros((256, 64)),   # 64 KiB, shardable on dim 0
            jnp.zeros((8,)),        # tiny -> replicated
            jnp.zeros((64, 512)),   # 128 KiB, shardable on dim 1
            jnp.zeros((512, 64)),   # 128 KiB, shardable on dim 0
        ]

    def test_byte_bounded_buckets(self):
        plan = plan_buckets(self._leaves(), dp=2, bucket_bytes=128 * 1024)
        assert plan.replicated == (1,)
        assert plan.shard_dims == (0, None, 1, 0)
        # 64K fits; adding 128K would exceed the 128K bound -> new bucket.
        assert plan.buckets == ((0,), (2,), (3,))
        assert plan.n_sharded == 3

    def test_single_bucket_when_large_bound(self):
        plan = plan_buckets(self._leaves(), dp=2, bucket_bytes=BUCKET_BYTES)
        assert plan.buckets == ((0, 2, 3),)

    def test_deterministic(self):
        a = plan_buckets(self._leaves(), dp=2, bucket_bytes=64 * 1024)
        b = plan_buckets(self._leaves(), dp=2, bucket_bytes=64 * 1024)
        assert a == b

    def test_min_size_respected(self):
        leaves = [jnp.zeros((64, 64))]  # 4096 elements < MIN_SIZE
        plan = plan_buckets(leaves, dp=2)
        assert plan.buckets == () and plan.replicated == (0,)

    def test_zero1_dim_matches_plan(self):
        leaves = self._leaves()
        plan = plan_buckets(leaves, dp=2)
        assert plan.shard_dims == tuple(
            zero1_dim(leaf, P(), 2) for leaf in leaves
        )


class TestTrainerIntegration:
    """Trainer.place_state's overlap routing and apply_tuned_step overlay."""

    def test_place_state_activates_overlapped_schedule(self):
        from deeplearning_mpi_tpu.train.trainer import Trainer

        mesh = _mesh(dp=2)
        trainer = Trainer(
            _lm_state(tx=build_optimizer("adam", 1e-2)), "lm", mesh,
            zero=True, overlap=True,
        )
        trainer.place_state()
        # The overlapped step is the only one carrying a bucket plan.
        assert hasattr(trainer.train_step, "bucket_plan")
        batch = _batches(mesh, n=1)[0]
        state, metrics = trainer.train_step(trainer.state, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_place_state_falls_back_on_unsupported(self):
        """dp=1 cannot overlap (nothing to reduce-scatter): place_state must
        log-and-fall-back to the GSPMD ZeRO-1 step, never raise."""
        from deeplearning_mpi_tpu.train.trainer import Trainer

        mesh = _mesh(dp=1)
        trainer = Trainer(
            _lm_state(tx=build_optimizer("adam", 1e-2)), "lm", mesh,
            zero=True, overlap=True,
        )
        trainer.place_state()  # must not raise
        assert not hasattr(trainer.train_step, "bucket_plan")
        batch = _batches(mesh, n=1)[0]
        state, metrics = trainer.train_step(trainer.state, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_apply_tuned_step_hit_applies_schedule(self, tmp_path):
        from deeplearning_mpi_tpu.compiler import autotune
        from deeplearning_mpi_tpu.train.trainer import Trainer

        mesh = _mesh(dp=2)
        db = autotune.TuningDB(tmp_path / "t.json")
        db.record_key(
            autotune.step_tuning_key("lm", (8, 16), mesh, jnp.float32),
            {"remat": "dots", "grad_accum": 2, "donate": True,
             "overlap": True},
            best_seconds=0.01, kernel="step",
        )
        trainer = Trainer(
            _lm_state(tx=build_optimizer("adam", 1e-2)), "lm", mesh,
            zero=True,
        )
        params = trainer.apply_tuned_step(
            db, model="lm", batch_size=8, seq_len=16
        )
        # remat is returned for the model builder; grad_accum and the
        # schedule choice are applied to the trainer directly.
        assert params["remat"] == "dots"
        assert trainer._step_kwargs["grad_accum"] == 2
        assert trainer.overlap is True

    def test_apply_tuned_step_never_raises_and_keeps_defaults(self, tmp_path):
        from deeplearning_mpi_tpu.train.trainer import Trainer

        mesh = _mesh(dp=2)

        def fresh():
            return Trainer(
                _lm_state(tx=build_optimizer("adam", 1e-2)), "lm", mesh,
                zero=True,
            )

        # Entry-less DB, corrupt file, and missing path: all miss cleanly.
        trainer = fresh()
        from deeplearning_mpi_tpu.compiler import autotune

        assert trainer.apply_tuned_step(
            autotune.TuningDB(), model="lm", batch_size=8, seq_len=16
        ) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert trainer.apply_tuned_step(
            str(bad), model="lm", batch_size=8, seq_len=16
        ) is None
        assert trainer.apply_tuned_step(
            str(tmp_path / "nope.json"), model="lm", batch_size=8, seq_len=16
        ) is None
        # Settings untouched on every miss.
        assert trainer.overlap is False
        assert trainer._step_kwargs.get("grad_accum", 1) == 1
