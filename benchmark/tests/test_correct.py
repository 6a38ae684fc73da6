"""``correct`` at test size: sound runs pass, the control (the reference in
the next lower precision, put in the program's place) fails, and so does the
harness's own run with the timed path broken underneath -- once for each fault
a cell can have. The skipped part is the harness's look for a chip."""

import json

import numpy as np
import pytest

from benchmark import check, reference, schedule
from benchmark import run as harness
from benchmark.tests import tiny

# readings at this size, CPU, seeds 5-7 (first gradient / change): program <= 4.5e-3 / 1.8e-3, control >= 0.021 / 0.0061
TRAIN_LIMITS = {"first_grad_norm_gap": 0.010, "param_change_norm_gap": 0.0035}
LIMITS = {
    "loss_step1_gap": 1.5e-3, "loss_step2_gap": 1.5e-3, "loss_step3_gap": 1.5e-3,
    "first_grad_norm_gap": 0.012, "param_change_norm_gap": 0.004,
}
LIMITS = {"lm-train-8k": TRAIN_LIMITS, "lm-train-8k-dp4": TRAIN_LIMITS, "lm-serve-chat": {"served_logit_gap": 0.1, "served_tokens_short_of_200": 200}}


def _run(root, cell, seed=5, seconds=1.5):
    code, result = harness.run_cell(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0
    return result


def _failed(result):
    return sorted(name for name, c in result["check"].items() if not c["ok"])


@pytest.fixture()
def root(tmp_path):
    return tiny.make_root(tmp_path, limits=LIMITS)


@pytest.mark.parametrize("cell", ["lm-train-8k", "lm-train-8k-dp4", "lm-serve-chat"])
def test_a_sound_run_is_correct(root, cell):
    import jax

    if cell.endswith("dp4") and len(jax.devices("cpu")) < 4:
        pytest.skip("needs four virtual CPU devices")
    result = _run(root, cell)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_the_training_control_is_not_correct(root):
    """The reference with every matmul's operands rounded to fp8, in the
    program's place, against the float32 reference, under the same limits."""
    cfg = json.loads((root / "benchmark/configs/mistral-7b-v0.1-d2.json").read_text())
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg["vocab_size"], (1, 64)).astype(np.int32) for _ in range(3)]
    ref = reference.follow_training(cfg, 5, batches)
    control = reference.follow_training(cfg, 5, batches, lower="float8_e4m3fn")
    gaps, _ = check.training(control, ref)
    assert any(gaps[name] > limit for name, limit in TRAIN_LIMITS.items()), gaps
    same, _ = check.training(ref, ref)
    assert max(same.values()) == 0.0


def test_the_serving_control_is_not_correct(root):
    """The tokens the fp8 reference puts first, judged like served tokens."""
    from benchmark.drivers import serve

    run = harness.open_run("lm-serve-chat", 5, 1.0, False, root=root, chips=tiny.cpu_chips)
    prompts = [schedule.prompt_ids(5, i, 30, run.config["vocab_size"]) for i in range(3)]
    own = []
    for p in prompts:  # greedy continuations by the reference itself: gap 0
        ids = list(p)
        import jax

        from benchmark import weights

        params = jax.jit(lambda w: weights.build(run.config, w, "bfloat16"))(weights.seed_words(5))
        for _ in range(6):
            logits = np.asarray(reference.serve_logits(run.config, params, np.asarray(ids, np.int32), np.asarray([len(ids) - 1])))
            ids.append(int(logits[0].argmax()))
        own.append((p, ids[len(p):]))
    gap, compared = serve.compare(run, own, 5)
    assert gap == 0.0 and compared == 18
    control, _ = serve.compare(run, own, 5, lower="float8_e4m3fn")
    assert control > LIMITS["lm-serve-chat"]["served_logit_gap"]


def test_a_step_that_leaves_the_parameters_unchanged_is_not_correct(root, monkeypatch):
    from deeplearning_mpi_tpu.train import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates", lambda params, updates: params)
    result = _run(root, "lm-train-8k")
    assert not result["correct"]
    assert result["check"]["param_change_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-4)


def _drop(monkeypatch, keep):
    """Break the loss underneath: only the positions ``keep(rows, seq)`` marks count."""
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.train import trainer

    sound = trainer.lm_cross_entropy
    monkeypatch.setattr(trainer, "lm_cross_entropy", lambda logits, tokens, mask=None: sound(logits, tokens, jnp.asarray(keep(*tokens.shape))))


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    _drop(monkeypatch, lambda rows, seq: np.arange(rows * seq).reshape(rows, seq) < rows * seq // 2)
    result = _run(root, "lm-train-8k")
    assert not result["correct"] and "first_grad_norm_gap" in _failed(result)


def test_the_exchange_between_chips_left_out_is_not_correct(root, monkeypatch):
    import jax

    if len(jax.devices("cpu")) < 4:
        pytest.skip("needs four virtual CPU devices")
    # every chip stepping on its own row's gradient, as chip 0 sees it
    _drop(monkeypatch, lambda rows, seq: np.arange(rows)[:, None].repeat(seq, 1) == 0)
    result = _run(root, "lm-train-8k-dp4")
    assert not result["correct"] and "first_grad_norm_gap" in _failed(result)


def test_a_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from deeplearning_mpi_tpu.serving.engine import ServingEngine

    sound = ServingEngine._done

    def altered(self, req, tok):
        if len(req.generated) == 3:
            req.generated[-1] = (tok + 1) % self.config.vocab_size
        return sound(self, req, tok)

    monkeypatch.setattr(ServingEngine, "_done", altered)
    result = _run(root, "lm-serve-chat")
    assert not result["correct"] and _failed(result) == ["served_logit_gap"]
