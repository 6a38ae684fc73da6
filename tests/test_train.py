"""Training-layer tests: step semantics, DP equivalence, NaN guard,
checkpoint/resume, and a miniature end-to-end learning run."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning_mpi_tpu.data import ShardedLoader, SyntheticCIFAR10
from deeplearning_mpi_tpu.data.cifar10 import eval_transform
from deeplearning_mpi_tpu.models import resnet18
from deeplearning_mpi_tpu.runtime.mesh import batch_sharding, replicated_sharding
from deeplearning_mpi_tpu.train import (
    Checkpointer,
    Trainer,
    create_train_state,
    make_eval_step,
    make_train_step,
)
from deeplearning_mpi_tpu.train.trainer import build_optimizer


def tiny_model():
    # Small enough for 1-core CPU, same codepaths (BN, stages, head).
    from deeplearning_mpi_tpu.models.resnet import ResNet, BasicBlock

    return ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, num_classes=10,
                  num_filters=8, stem="cifar")


def make_state(tx=None, seed=0):
    model = tiny_model()
    tx = tx or build_optimizer("sgd", 0.05, momentum=0.9, weight_decay=1e-5)
    return create_train_state(
        model, jax.random.key(seed), jnp.zeros((1, 32, 32, 3)), tx
    )


def make_batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.normal(size=(n, 32, 32, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
    }


class TestTrainStep:
    @pytest.mark.slow
    def test_step_advances_and_loss_finite(self):
        state = make_state()
        step = make_train_step("classification", donate=False)
        new_state, metrics = step(state, make_batch())
        assert int(new_state.step) == 1
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["finite"]) == 1.0

    def test_grad_accum_matches_full_batch(self):
        """On a batch-stat-free model, grad_accum=4 must produce the same
        update as one full-batch step (mean of equal-sized chunk means ==
        full-batch mean), modulo f32 summation order."""
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        # Plain SGD: the update is linear in the grads, so the only allowed
        # difference is f32 summation order. (Adam at step 1 is ~sign(g)*lr,
        # which amplifies associativity noise on near-zero grads.)
        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)

        def fresh():
            return create_train_state(
                model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
            )

        batch = {
            "tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, 256, (8, 16)), jnp.int32
            )
        }
        s1, m1 = make_train_step("lm", donate=False)(fresh(), batch)
        s4, m4 = make_train_step("lm", donate=False, grad_accum=4)(fresh(), batch)
        np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s4.params), jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_grad_accum_matches_full_batch_ragged_mask(self):
        """With a RAGGED per-token mask (chunks carry very different
        valid-token counts), chunked accumulation must still equal the
        full-batch masked mean: chunks combine by valid-token weight, not a
        plain mean of chunk means (which would up-weight sparse chunks)."""
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)

        def fresh():
            return create_train_state(
                model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
            )

        rng = np.random.default_rng(1)
        mask = np.ones((8, 16), np.float32)
        mask[0:2, 2:] = 0.0   # chunk 0: almost everything masked
        mask[4, 8:] = 0.0     # chunk 2: half a row masked
        batch = {
            "tokens": jnp.asarray(rng.integers(0, 256, (8, 16)), jnp.int32),
            "mask": jnp.asarray(mask),
        }
        s1, m1 = make_train_step("lm", donate=False)(fresh(), batch)
        s4, m4 = make_train_step("lm", donate=False, grad_accum=4)(fresh(), batch)
        np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s4.params), jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_grad_accum_moe_aux_stays_close(self):
        """aux_weight > 0 with grad_accum: the aux load-balance loss is
        nonlinear in batch composition, so chunked is not bit-equal to
        full-batch — but the reported data loss must match exactly (aux is
        excluded from it) and the update must stay close and finite."""
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(
            config=TransformerConfig.tiny_moe(num_experts=4), dtype=jnp.float32
        )
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)

        def fresh():
            return create_train_state(
                model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), tx
            )

        batch = {
            "tokens": jnp.asarray(
                np.random.default_rng(2).integers(0, 256, (8, 16)), jnp.int32
            )
        }
        s1, m1 = make_train_step("lm", donate=False, aux_weight=0.01)(
            fresh(), batch
        )
        s2, m2 = make_train_step("lm", donate=False, aux_weight=0.01, grad_accum=2)(
            fresh(), batch
        )
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s2.params), jax.tree.leaves(s1.params)):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)

    def test_grad_accum_batchnorm_chunks_stats(self):
        """With BatchNorm, each chunk normalizes over its own examples (the
        same semantics as DDP's per-replica BN stats), so chunked training is
        deliberately NOT bit-equal to full-batch — but it must stay close and
        must advance the EMA stats off init."""
        batch = make_batch()
        s1, m1 = make_train_step("classification", donate=False)(
            make_state(), batch
        )
        s4, m4 = make_train_step("classification", donate=False, grad_accum=4)(
            make_state(), batch
        )
        np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]), rtol=0.02)
        init_stats = jax.tree.leaves(make_state().batch_stats)
        moved = [
            not np.allclose(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(s4.batch_stats), init_stats)
        ]
        assert any(moved)

    def test_grad_accum_matches_full_batch_unet(self):
        """UNet (BatchNorm) under grad_accum, on a duplicated-halves batch:
        each chunk's batch statistics equal the full batch's by construction
        (concat([half, half]) normalizes identically whole or chunked), so
        the per-chunk-BN caveat of test_grad_accum_batchnorm_chunks_stats
        vanishes and the accumulation arithmetic itself must reproduce the
        full-batch update to tight tolerance. (The EMA batch_stats still
        advance once per chunk — documented semantics — so only loss and
        params are held to the tight bound.)"""
        from deeplearning_mpi_tpu.models import UNet

        model = UNet(out_classes=1, features=(4, 8))
        tx = build_optimizer("sgd", 1e-2, momentum=0.0)

        def fresh():
            return create_train_state(
                model, jax.random.key(0), jnp.zeros((1, 16, 16, 3)), tx
            )

        rng = np.random.default_rng(3)
        half_img = rng.normal(size=(4, 16, 16, 3)).astype(np.float32)
        half_mask = (rng.random((4, 16, 16)) > 0.5).astype(np.float32)
        batch = {
            "image": jnp.asarray(np.concatenate([half_img, half_img])),
            "mask": jnp.asarray(np.concatenate([half_mask, half_mask])),
        }
        s1, m1 = make_train_step("segmentation", donate=False)(fresh(), batch)
        s2, m2 = make_train_step("segmentation", donate=False, grad_accum=2)(
            fresh(), batch
        )
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s2.params), jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_grad_accum_indivisible_raises(self):
        step = make_train_step("classification", donate=False, grad_accum=3)
        with pytest.raises(ValueError, match="divisible"):
            step(make_state(), make_batch(n=16))

    def test_grad_accum_indivisible_names_offending_leaf(self):
        """The error must identify WHICH batch leaf failed and its shape —
        'not divisible' alone sends the user hunting through every input."""
        step = make_train_step("classification", donate=False, grad_accum=3)
        with pytest.raises(
            ValueError, match=r"image.*\(16, 32, 32, 3\).*grad_accum=3"
        ):
            step(make_state(), make_batch(n=16))
        # LM path with a mask: same naming contract through the other task.
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32),
            build_optimizer("sgd", 1e-2, momentum=0.0),
        )
        lm_step = make_train_step("lm", donate=False, grad_accum=4)
        lm_batch = {
            "tokens": jnp.zeros((3, 16), jnp.int32),
            "mask": jnp.ones((3, 16), jnp.float32),
        }
        with pytest.raises(ValueError, match=r"\(3, 16\).*grad_accum=4"):
            lm_step(state, lm_batch)

    def test_params_change(self):
        state = make_state()
        step = make_train_step("classification", donate=False)
        new_state, _ = step(state, make_batch())
        diffs = jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), state.params, new_state.params
        )
        assert max(jax.tree.leaves(diffs)) > 0

    @pytest.mark.slow
    def test_nonfinite_loss_skips_update(self):
        state = make_state()
        step = make_train_step("classification", donate=False)
        bad = make_batch()
        bad["image"] = bad["image"].at[0, 0, 0, 0].set(jnp.nan)
        new_state, metrics = step(state, bad)
        assert float(metrics["finite"]) == 0.0
        # parameters unchanged (update skipped, train.py:186-188 parity)...
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(new_state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # ...but the step counter still advances (batch consumed)
        assert int(new_state.step) == 1

    @pytest.mark.slow
    def test_dp_equals_single_device(self, mesh):
        """The DDP-parity property: training on an 8-way sharded batch gives
        the same parameters as unsharded training on the same global batch."""
        batch = make_batch(16, seed=7)
        step = make_train_step("classification", donate=False)

        state_a = make_state(seed=1)
        sharded_batch = {
            "image": jax.device_put(batch["image"], batch_sharding(mesh)),
            "label": jax.device_put(batch["label"], batch_sharding(mesh, ndim=1)),
        }
        state_a = jax.device_put(state_a, replicated_sharding(mesh))
        for _ in range(3):
            state_a, _ = step(state_a, sharded_batch)

        state_b = make_state(seed=1)
        for _ in range(3):
            state_b, _ = step(state_b, batch)

        for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @pytest.mark.slow
    def test_grad_clip_engages(self):
        tx = build_optimizer("adam", 1e-3, clip_norm=1e-6)
        state = make_state(tx=tx)
        step = make_train_step("classification", donate=False)
        new_state, _ = step(state, make_batch())
        # with clip 1e-6 and lr 1e-3 the update magnitude must be tiny
        max_delta = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(
                jax.tree.leaves(state.params), jax.tree.leaves(new_state.params)
            )
        )
        assert max_delta < 2e-3  # adam normalizes, but clipped grads keep it small


class _ListLoader:
    """Minimal loader stub: replays fixed batches for any epoch."""

    def __init__(self, batches):
        self.batches = batches

    def epoch(self, epoch):
        return iter(self.batches)


class TestEMA:
    def _ema_state(self):
        model = tiny_model()
        tx = build_optimizer("sgd", 0.05, momentum=0.9)
        return create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 32, 32, 3)), tx, ema=True
        )

    def test_initialized_to_params(self):
        state = self._ema_state()
        for e, p in zip(
            jax.tree.leaves(state.ema_params), jax.tree.leaves(state.params)
        ):
            np.testing.assert_array_equal(np.asarray(e), np.asarray(p))

    def test_off_by_default_keeps_tree(self):
        # ema_params=None must not add leaves: existing checkpoints keep
        # their tree structure exactly.
        state = make_state()
        assert state.ema_params is None
        n_core = len(jax.tree.leaves(
            (state.step, state.params, state.batch_stats, state.opt_state)
        ))
        assert len(jax.tree.leaves(state)) == n_core

    def test_update_rule_matches_manual(self):
        d = 0.9
        state = self._ema_state()
        step = make_train_step("classification", donate=False, ema_decay=d)
        batch = make_batch()
        manual = jax.tree.map(jnp.copy, state.params)
        for _ in range(3):
            state, _ = step(state, batch)
            manual = jax.tree.map(
                lambda e, p: d * e + (1 - d) * p, manual, state.params
            )
        for e, m in zip(
            jax.tree.leaves(state.ema_params), jax.tree.leaves(manual)
        ):
            np.testing.assert_allclose(
                np.asarray(e), np.asarray(m), rtol=1e-6, atol=1e-7
            )
        # And the EMA genuinely lags the raw params.
        diffs = [
            float(jnp.max(jnp.abs(e - p)))
            for e, p in zip(
                jax.tree.leaves(state.ema_params), jax.tree.leaves(state.params)
            )
        ]
        assert max(diffs) > 0

    def test_decay_without_ema_state_raises(self):
        state = make_state()
        step = make_train_step("classification", donate=False, ema_decay=0.9)
        with pytest.raises(ValueError, match="tracks no EMA"):
            step(state, make_batch())

    def test_checkpoint_roundtrips_ema_bits(self, tmp_path):
        # The silent-drop failure mode: _arrays_only once omitted ema_params,
        # so restore kept the template's fresh EMA and eval quietly served
        # init-tinted weights. Bits must survive the roundtrip.
        state = self._ema_state()
        step = make_train_step("classification", donate=False, ema_decay=0.9)
        for _ in range(2):
            state, _ = step(state, make_batch())
        ck = Checkpointer(tmp_path / "ck")
        ck.save(state, epoch=0)
        template = self._ema_state()
        restored = ck.restore(template)
        ck.close()
        for a, b in zip(
            jax.tree.leaves(state.ema_params),
            jax.tree.leaves(restored.ema_params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_eval_uses_ema_weights(self):
        state = self._ema_state()
        batch = make_batch()
        eval_step = make_eval_step("classification")
        base = float(eval_step(state, batch)["loss"])
        # Corrupt the RAW params only: eval must be insensitive (it reads
        # the EMA), and corrupting the EMA must move it.
        corrupt = lambda t: jax.tree.map(lambda x: x + 1.0, t)  # noqa: E731
        same = float(
            eval_step(state.replace(params=corrupt(state.params)), batch)["loss"]
        )
        moved = float(
            eval_step(
                state.replace(ema_params=corrupt(state.ema_params)), batch
            )["loss"]
        )
        assert same == pytest.approx(base)
        assert moved != pytest.approx(base)


class TestNonFiniteHandling:
    @pytest.mark.slow
    def test_nan_batch_excluded_from_epoch_mean(self, mesh):
        from deeplearning_mpi_tpu.train.trainer import Trainer

        good = make_batch(seed=1)
        poisoned = make_batch(seed=2)
        poisoned["image"] = poisoned["image"].at[0, 0, 0, 0].set(jnp.nan)
        trainer = Trainer(make_state(), "classification", mesh)
        # Oracle: same state/batches without the poisoned batch in between.
        oracle = Trainer(make_state(), "classification", mesh)
        oracle_stats = oracle.run_epoch(_ListLoader([good, good]), epoch=0)
        stats = trainer.run_epoch(_ListLoader([good, poisoned, good]), epoch=0)
        # One NaN batch: skipped by the step, excluded from the mean — the
        # denominator must be the finite count (2), not the batch count (3).
        assert stats["loss"] == pytest.approx(oracle_stats["loss"], abs=1e-6)


class TestEvalPaddingExclusion:
    @pytest.mark.slow
    def test_evaluate_matches_exact_dataset_metrics(self, mesh):
        from deeplearning_mpi_tpu.data.cifar10 import SyntheticCIFAR10, eval_transform
        from deeplearning_mpi_tpu.data.loader import ShardedLoader
        from deeplearning_mpi_tpu.train.trainer import Trainer

        ds = SyntheticCIFAR10(40)  # 2 full batches of 16 + 8-row padded tail
        loader = ShardedLoader(
            ds, 16, mesh, shuffle=False, drop_last=False, transform=eval_transform
        )
        state = make_state()
        trainer = Trainer(state, "classification", mesh)
        result = trainer.evaluate(loader)
        # Oracle: run the whole dataset (no padding) through the model once.
        examples = [ds[i] for i in range(len(ds))]
        batch = eval_transform(
            {
                "image": np.stack([ex["image"] for ex in examples]),
                "label": np.stack([ex["label"] for ex in examples]),
            },
            np.random.default_rng(0),
        )
        logits = state.apply_fn(
            state.variables(), jnp.asarray(batch["image"]), train=False
        )
        expected = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(batch["label"])))
        assert result["accuracy"] == pytest.approx(expected, abs=1e-6)


class TestEvalStep:
    @pytest.mark.slow
    def test_classification_metrics(self):
        state = make_state()
        ev = make_eval_step("classification")
        metrics = ev(state, make_batch())
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0
        assert np.isfinite(float(metrics["loss"]))

    @pytest.mark.slow
    def test_segmentation_metrics(self):
        from deeplearning_mpi_tpu.models import UNet

        model = UNet(out_classes=1, features=(4, 8))
        tx = build_optimizer("adam", 1e-3)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16, 16, 3)), tx
        )
        ev = make_eval_step("segmentation")
        batch = {
            "image": jnp.zeros((2, 16, 16, 3)),
            "mask": jnp.zeros((2, 16, 16)),
        }
        metrics = ev(state, batch)
        assert 0.0 <= float(metrics["dice"]) <= 1.0


class TestCheckpoint:
    @pytest.mark.slow
    def test_roundtrip(self, tmp_path):
        state = make_state()
        step = make_train_step("classification", donate=False)
        state, _ = step(state, make_batch())
        ckpt = Checkpointer(tmp_path / "ckpt")
        ckpt.save(state, epoch=0)
        assert ckpt.latest_epoch() == 0

        restored = ckpt.restore(make_state(seed=99))  # template with different init
        assert int(restored.step) == int(state.step)
        for a, b in zip(jax.tree.leaves(restored.params), jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # optimizer state (momentum buffers) restored too — unlike the
        # reference's weights-only .pth (SURVEY.md §5.4)
        for a, b in zip(jax.tree.leaves(restored.opt_state), jax.tree.leaves(state.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ckpt.close()

    @pytest.mark.slow
    def test_restore_empty_raises(self, tmp_path):
        ckpt = Checkpointer(tmp_path / "none")
        with pytest.raises(FileNotFoundError):
            ckpt.restore(make_state())
        ckpt.close()

    @pytest.mark.slow
    def test_keeps_history(self, tmp_path):
        state = make_state()
        ckpt = Checkpointer(tmp_path / "ckpt", max_to_keep=2)
        for e in range(3):
            ckpt.save(state, epoch=e)
        assert ckpt.latest_epoch() == 2
        assert ckpt.manager.all_steps() == [1, 2]
        ckpt.close()


class TestTrainerEndToEnd:
    @pytest.mark.slow
    def test_learns_synthetic_cifar(self, mesh, tmp_path):
        """Mini e2e: loss drops and accuracy beats chance on learnable data."""
        ds = SyntheticCIFAR10(128, seed=0)
        loader = ShardedLoader(ds, 32, mesh, shuffle=True, transform=eval_transform)
        state = make_state(tx=build_optimizer("sgd", 0.1, momentum=0.9))
        trainer = Trainer(
            state, "classification", mesh,
            checkpointer=Checkpointer(tmp_path / "ckpt"), eval_every=10,
        )
        trainer.replicate_state()
        history = trainer.fit(loader, 12, eval_loader=loader)
        assert history[-1]["loss"] < history[0]["loss"]
        final_eval = trainer.evaluate(loader)
        assert final_eval["accuracy"] > 0.4  # chance = 0.1
        trainer.checkpointer.close()

    @pytest.mark.slow
    def test_resume_continues(self, mesh, tmp_path):
        ds = SyntheticCIFAR10(64, seed=0)
        loader = ShardedLoader(ds, 32, mesh, shuffle=True, transform=eval_transform)
        ckpt = Checkpointer(tmp_path / "ckpt")
        trainer = Trainer(make_state(), "classification", mesh, checkpointer=ckpt)
        trainer.replicate_state()
        trainer.fit(loader, 1)
        steps_after_one_epoch = int(trainer.state.step)
        ckpt.close()

        ckpt2 = Checkpointer(tmp_path / "ckpt")
        assert ckpt2.latest_epoch() == 0
        restored = ckpt2.restore(make_state(seed=5))
        assert int(restored.step) == steps_after_one_epoch
        ckpt2.close()


class TestOptimizerFamilies:
    """build_optimizer beyond the reference pair (sgd/adam): adamw,
    adafactor, lion. Each must actually optimize through the standard train
    step, and adafactor must deliver its factored-moment memory claim."""

    @pytest.mark.parametrize("name", ["adamw", "adafactor", "lion"])
    def test_family_learns(self, name):
        lr = {"adamw": 1e-3, "adafactor": 1e-2, "lion": 1e-4}[name]
        state = make_state(
            tx=build_optimizer(name, lr, weight_decay=1e-4, clip_norm=1.0)
        )
        step = make_train_step("classification", donate=False)
        batch = make_batch(n=8)
        _, first = step(state, batch)
        for _ in range(12):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["loss"]) < float(first["loss"])

    def test_adafactor_factors_large_matrices(self):
        """A [256, 256] kernel costs Adam 2×256² f32 moments; adafactor keeps
        O(rows+cols) factors — the reason it's the TPU large-model default."""
        params = {"w": jnp.zeros((256, 256))}
        size = lambda tree: sum(  # noqa: E731
            leaf.size for leaf in jax.tree.leaves(tree)
            if hasattr(leaf, "size")
        )
        adam_sz = size(build_optimizer("adam", 1e-3).init(params))
        fact_sz = size(build_optimizer("adafactor", 1e-2).init(params))
        assert adam_sz >= 2 * 256 * 256
        assert fact_sz < 0.1 * adam_sz

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError, match="unknown optimizer"):
            build_optimizer("adagrad", 1e-3)


class TestLRSchedule:
    def test_constant_is_bare_float(self):
        from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

        assert build_lr_schedule(0.1, "constant") == 0.1

    def test_warmup_then_cosine(self):
        from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

        sched = build_lr_schedule(0.1, "cosine", warmup_steps=10, decay_steps=100)
        assert float(sched(0)) == 0.0
        np.testing.assert_allclose(float(sched(10)), 0.1, rtol=1e-6)
        assert float(sched(55)) < 0.1
        np.testing.assert_allclose(float(sched(100)), 0.0, atol=1e-8)

    def test_linear_and_warmup_constant(self):
        from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

        lin = build_lr_schedule(0.2, "linear", warmup_steps=4, decay_steps=24)
        np.testing.assert_allclose(float(lin(4)), 0.2, rtol=1e-6)
        np.testing.assert_allclose(float(lin(14)), 0.1, rtol=1e-5)
        const = build_lr_schedule(0.2, "constant", warmup_steps=4)
        np.testing.assert_allclose(float(const(2)), 0.1, rtol=1e-5)
        np.testing.assert_allclose(float(const(400)), 0.2, rtol=1e-6)

    def test_decay_shorter_than_warmup_raises(self):
        from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

        with pytest.raises(ValueError, match="decay_steps"):
            build_lr_schedule(0.1, "cosine", warmup_steps=50, decay_steps=40)

    def test_scheduled_optimizer_trains(self):
        """End-to-end: a cosine schedule drives the SGD step (optax resolves
        the LR from the optimizer step count inside state.tx)."""
        from deeplearning_mpi_tpu.train.trainer import build_lr_schedule

        tx = build_optimizer(
            "sgd",
            build_lr_schedule(0.05, "cosine", warmup_steps=2, decay_steps=20),
            momentum=0.9,
        )
        state = make_state(tx=tx)
        step = make_train_step("classification", donate=False)
        batch = make_batch()
        p0 = jax.tree.leaves(state.params)[0].copy()
        for _ in range(3):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert not np.allclose(np.asarray(jax.tree.leaves(state.params)[0]), np.asarray(p0))


class TestSegLossSelector:
    def test_variants_and_composition(self):
        from deeplearning_mpi_tpu.train.trainer import _task_loss

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(2, 4, 4, 1)), jnp.float32)
        batch = {
            "mask": jnp.asarray(
                (rng.random((2, 4, 4)) > 0.5).astype(np.float32)
            )
        }
        bce = float(_task_loss("segmentation")(logits, batch))
        dice = float(_task_loss("segmentation", seg_loss="dice")(logits, batch))
        both = float(
            _task_loss("segmentation", seg_loss="bce_dice")(logits, batch)
        )
        assert bce != pytest.approx(dice)
        assert both == pytest.approx(bce + dice, rel=1e-6)
        with pytest.raises(ValueError, match="seg_loss"):
            _task_loss("segmentation", seg_loss="jaccard")

    def test_dice_training_step_decreases_dice_loss(self):
        # A tiny conv head trained under seg_loss='dice' must reduce the
        # dice objective — the selector reaches the jitted step end to end.
        import flax.linen as nn

        from deeplearning_mpi_tpu.train import create_train_state
        from deeplearning_mpi_tpu.train.trainer import (
            build_optimizer,
            make_train_step,
        )

        class Head(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                return nn.Conv(1, (3, 3), padding="SAME")(x)

        rng = np.random.default_rng(1)
        images = jnp.asarray(rng.normal(size=(8, 8, 8, 3)), jnp.float32)
        masks = jnp.asarray(
            (images.sum(-1) > 0).astype(np.float32)
        )
        batch = {"image": images, "mask": masks}
        state = create_train_state(
            Head(), jax.random.key(0), jnp.zeros((1, 8, 8, 3)),
            build_optimizer("adam", 1e-2),
        )
        step = make_train_step("segmentation", donate=False, seg_loss="dice")
        losses = []
        for _ in range(10):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]


class TestStepCompilerOptions:
    """The TPU compile options of the train step are chosen from the devices
    the step is placed on; XLA:CPU refuses them, so a CPU step gets none."""

    @pytest.mark.parametrize(
        "platforms, tpu",
        [(("tpu",) * 4, True), (("tpu",), True), (("cpu",) * 8, False),
         (("tpu", "cpu"), False), ((), False)],
        ids=["four_tpus", "one_tpu", "cpus", "mixed", "no_devices"],
    )
    def test_options_only_when_every_device_is_a_tpu(self, platforms, tpu):
        from types import SimpleNamespace

        from deeplearning_mpi_tpu.train.trainer import (
            TPU_STEP_COMPILER_OPTIONS,
            step_compiler_options,
        )

        devices = [SimpleNamespace(platform=p) for p in platforms]
        assert step_compiler_options(devices) == (
            TPU_STEP_COMPILER_OPTIONS if tpu else None
        )

    @pytest.mark.parametrize("zero", [False, True], ids=["replicated", "zero1"])
    def test_cpu_step_compiles_runs_and_holds_no_async_collective(self, mesh, zero):
        """On the 8-device CPU mesh the LM step, told its mesh, compiles
        without a TPU option (XLA:CPU would refuse one), runs, and its
        compiled HLO holds the gradient reductions as plain (synchronous)
        collectives; with ZeRO-1 state as well."""
        from deeplearning_mpi_tpu.compiler import aot
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
        from deeplearning_mpi_tpu.parallel import infer_state_sharding, shard_state

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        state = shard_state(create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), optax.adam(1e-2),
        ), mesh, zero=zero)
        kw = {"mesh": mesh}
        if zero:
            kw["state_shardings"] = infer_state_sharding(state, mesh, zero=True)
        batch = {"tokens": jax.device_put(
            jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 16)), jnp.int32),
            batch_sharding(mesh, 2),
        )}
        compiled = make_train_step("lm", donate=False, **kw).lower(state, batch).compile()
        n_async, n_sync = aot.collective_counts(compiled)
        assert n_async == 0 and n_sync > 0
        new_state, metrics = compiled(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == 1

    def test_warmup_gauges_read_the_cpu_step(self, mesh):
        from deeplearning_mpi_tpu.compiler import aot
        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(config=TransformerConfig.tiny(), dtype=jnp.float32)
        state = create_train_state(
            model, jax.random.key(0), jnp.zeros((1, 16), jnp.int32), optax.sgd(1e-2),
        )
        trainer = Trainer(state, "lm", mesh)
        trainer.place_state()
        batch = {"tokens": jax.device_put(
            jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 16)), jnp.int32),
            batch_sharding(mesh, 2),
        )}
        prog = trainer.warmup(batch)
        assert trainer.metrics.gauge("train_step_async_collectives").value == 0
        assert (trainer.metrics.gauge("train_step_sync_collectives").value
                == aot.collective_counts(prog.compiled)[1] > 0)
        _, metrics = trainer.train_step(trainer.state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert trainer.train_step.fallback_calls == 0
