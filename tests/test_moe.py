"""MoE layer + expert-parallel sharding tests (8 virtual CPU devices)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning_mpi_tpu.models import MoEMLP, TransformerConfig, TransformerLM, collect_aux_loss
from deeplearning_mpi_tpu.models.moe import AUX_COLLECTION
from deeplearning_mpi_tpu.parallel import shard_state
from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh


def _init(model, x, rng=0):
    return model.init(jax.random.key(rng), x)


class TestMoEMLP:
    def test_output_shape_finite(self):
        model = MoEMLP(d_ff=16, dtype=jnp.float32, num_experts=4, top_k=2)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 12)), jnp.float32)
        params = _init(model, x)
        out = model.apply(params, x)
        assert out.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_expert_choice_fills_every_capacity_slot(self):
        """EC routing: each expert selects exactly its capacity of tokens
        (balanced by construction), distinct tokens per expert, and sows NO
        aux loss."""
        model = MoEMLP(
            d_ff=16, dtype=jnp.float32, num_experts=4, top_k=2,
            routing="expert_choice",
        )
        x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 12)), jnp.float32)
        params = _init(model, x)
        out, mutated = model.apply(params, x, mutable=[AUX_COLLECTION])
        assert out.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(out)))
        assert float(collect_aux_loss(mutated)) == 0.0  # nothing sown

        # Reconstruct the combine tensor's support: run the routing helper
        # directly on the router's probabilities.
        logits = x @ params["params"]["router"]["kernel"]
        probs = jax.nn.softmax(logits, axis=-1)
        capacity = 5  # ceil(2 * 8 * 1.25 / 4)
        combine, aux, uncovered = model._expert_choice(probs, capacity)
        assert aux is None
        assert 0.0 <= float(uncovered) <= 1.0
        dispatch = (combine > 0).astype(np.float32)  # [B, S, E, C]
        # every (expert, slot) holds exactly one token
        np.testing.assert_array_equal(
            np.asarray(dispatch.sum(axis=1)), np.ones((2, 4, capacity))
        )
        # one expert never takes the same token in two slots
        assert float(jnp.max(dispatch.sum(axis=-1))) == 1.0

    def test_expert_choice_grads_reach_router_and_experts(self):
        model = MoEMLP(
            d_ff=16, dtype=jnp.float32, num_experts=4, top_k=2,
            routing="expert_choice",
        )
        x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 12)), jnp.float32)
        params = _init(model, x)

        def loss(p):
            return jnp.sum(model.apply(p, x) ** 2)

        grads = jax.grad(loss)(params)["params"]
        assert float(jnp.max(jnp.abs(grads["router"]["kernel"]))) > 0
        assert float(jnp.max(jnp.abs(grads["experts_down"]))) > 0

    def test_unknown_routing_rejected(self):
        model = MoEMLP(d_ff=16, num_experts=2, routing="mystery")
        x = jnp.zeros((1, 4, 8))
        with pytest.raises(ValueError, match="routing"):
            _init(model, x)

    def test_single_expert_matches_manual_swiglu(self):
        """E=1, k=1, ample capacity: routing is the identity, so the layer
        must equal a plain SwiGLU computed from its own expert weights."""
        model = MoEMLP(
            d_ff=16, dtype=jnp.float32, num_experts=1, top_k=1, capacity_factor=2.0
        )
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 6, 8)), jnp.float32)
        params = _init(model, x)
        out = model.apply(params, x)
        p = params["params"]
        hidden = jax.nn.silu(x @ p["experts_gate"][0]) * (x @ p["experts_up"][0])
        expected = hidden @ p["experts_down"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_capacity_drop_zeroes_some_tokens(self):
        """With capacity 1 and many tokens, most tokens are dropped and their
        output rows are exact zeros (residual passthrough)."""
        model = MoEMLP(
            d_ff=8, dtype=jnp.float32, num_experts=2, top_k=1,
            capacity_factor=1e-6,  # floors to capacity=1
        )
        x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 16, 8)), jnp.float32)
        params = _init(model, x)
        out = np.asarray(model.apply(params, x))
        zero_rows = np.all(out == 0.0, axis=-1).sum()
        # 16 tokens, 2 experts × capacity 1 → at least 14 dropped.
        assert zero_rows >= 14

    def test_aux_loss_sown_and_near_one_when_balanced(self):
        model = MoEMLP(d_ff=8, dtype=jnp.float32, num_experts=4, top_k=1)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 32, 8)), jnp.float32)
        params = _init(model, x)
        _, mutated = model.apply(params, x, mutable=[AUX_COLLECTION])
        aux = collect_aux_loss(mutated)
        # Switch aux loss is ≥ 1 with equality at perfect balance; a random
        # router on random inputs sits near 1.
        assert 0.9 < float(aux) < 3.0

    def test_collect_aux_loss_empty_tree_is_zero(self):
        assert float(collect_aux_loss({})) == 0.0

    def test_dropped_fraction_sown_nonzero_under_forced_imbalance(self):
        """capacity 1 with 16 tokens on 2 experts: >= 14/16 of claims must
        overflow — the sown dropped fraction surfaces it (round-4 weak #6:
        routing collapse degraded silently)."""
        from deeplearning_mpi_tpu.models.moe import (
            METRIC_COLLECTION,
            collect_dropped_fraction,
        )

        model = MoEMLP(
            d_ff=8, dtype=jnp.float32, num_experts=2, top_k=1,
            capacity_factor=1e-6,  # floors to capacity=1
        )
        x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 16, 8)), jnp.float32)
        params = _init(model, x)
        _, mutated = model.apply(
            params, x, mutable=[AUX_COLLECTION, METRIC_COLLECTION]
        )
        drop = collect_dropped_fraction(mutated)
        assert drop is not None
        assert float(drop) >= 14 / 16

    def test_dropped_fraction_zero_when_capacity_ample(self):
        from deeplearning_mpi_tpu.models.moe import (
            METRIC_COLLECTION,
            collect_dropped_fraction,
        )

        # capacity_factor E/k makes every expert able to absorb all tokens.
        model = MoEMLP(
            d_ff=8, dtype=jnp.float32, num_experts=2, top_k=1,
            capacity_factor=2.0,
        )
        x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 8, 8)), jnp.float32)
        params = _init(model, x)
        _, mutated = model.apply(
            params, x, mutable=[AUX_COLLECTION, METRIC_COLLECTION]
        )
        assert float(collect_dropped_fraction(mutated)) == 0.0

    def test_dropped_fraction_none_for_dense_tree(self):
        from deeplearning_mpi_tpu.models.moe import collect_dropped_fraction

        assert collect_dropped_fraction({}) is None

    def test_expert_choice_sows_uncovered_token_fraction(self):
        """EC fills every capacity SLOT by construction, but a token picked
        by no expert still skips its MLP — with capacity 1, two experts
        cover at most 2 of 8 tokens, so the sown fraction must be >= 6/8."""
        from deeplearning_mpi_tpu.models.moe import (
            METRIC_COLLECTION,
            collect_dropped_fraction,
        )

        model = MoEMLP(
            d_ff=8, dtype=jnp.float32, num_experts=2, top_k=1,
            capacity_factor=1e-6, routing="expert_choice",
        )
        x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 8, 8)), jnp.float32)
        params = _init(model, x)
        _, mutated = model.apply(
            params, x, mutable=[AUX_COLLECTION, METRIC_COLLECTION]
        )
        drop = collect_dropped_fraction(mutated)
        assert drop is not None and float(drop) >= 6 / 8

    def test_grads_flow_to_experts_and_router(self):
        model = MoEMLP(d_ff=8, dtype=jnp.float32, num_experts=2, top_k=2)
        x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 8, 8)), jnp.float32)
        params = _init(model, x)

        def loss(p):
            out, mutated = model.apply(p, x, mutable=[AUX_COLLECTION])
            return jnp.sum(out**2) + 0.01 * collect_aux_loss(mutated)

        grads = jax.grad(loss)(params)["params"]
        for name in ("experts_gate", "experts_up", "experts_down"):
            assert float(jnp.linalg.norm(grads[name])) > 0, name
        assert float(jnp.linalg.norm(grads["router"]["kernel"])) > 0


class TestMoETransformer:
    def test_moe_lm_forward_and_aux(self):
        cfg = TransformerConfig.tiny_moe(num_experts=4)
        model = TransformerLM(config=cfg, dtype=jnp.float32)
        tokens = jnp.ones((2, 16), jnp.int32)
        params = model.init(jax.random.key(0), tokens)
        # expert stacks exist with the path marker the EP rule keys on
        flat = jax.tree_util.tree_flatten_with_path(params["params"])[0]
        expert_leaves = [
            leaf for path, leaf in flat
            if "experts" in jax.tree_util.keystr(path)
        ]
        assert len(expert_leaves) == 3 * cfg.num_layers
        assert all(leaf.shape[0] == 4 for leaf in expert_leaves)
        logits, mutated = model.apply(params, tokens, mutable=[AUX_COLLECTION])
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert float(collect_aux_loss(mutated)) > 0


@pytest.mark.slow
class TestExpertParallelSharding:
    def test_expert_stack_sharded_over_expert_and_model_axes(self):
        mesh = create_mesh(MeshSpec(data=2, expert=2, model=2))
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=4,
            d_model=8, d_ff=16, moe_experts=4,
        )
        model = TransformerLM(config=cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0), jnp.ones((2, 8), jnp.int32))
        sharded = shard_state(params, mesh)
        stack = sharded["params"]["layer_0"]["mlp"]["experts_gate"]
        assert stack.sharding.spec == P("expert", None, "model")
        down = sharded["params"]["layer_0"]["mlp"]["experts_down"]
        assert down.sharding.spec == P("expert", "model", None)
        router = sharded["params"]["layer_0"]["mlp"]["router"]["kernel"]
        assert router.sharding.spec == P()

    def test_sharded_forward_matches_unsharded(self):
        mesh = create_mesh(MeshSpec(data=2, expert=4))
        cfg = TransformerConfig.tiny_moe(num_experts=4)
        model = TransformerLM(config=cfg, dtype=jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (4, 16)), jnp.int32
        )
        params = model.init(jax.random.key(0), tokens)
        expected = model.apply(params, tokens)

        sharded_params = shard_state(params, mesh)
        sharded_tokens = jax.device_put(
            tokens, NamedSharding(mesh, P("data", None))
        )
        got = jax.jit(model.apply)(sharded_params, sharded_tokens)
        np.testing.assert_allclose(
            np.asarray(expected), np.asarray(got), atol=2e-4
        )


# -- the two forms of the dropless layer (PR 37) ------------------------------

def _dropless_args(n_exp, d=32, f=16, seed=37):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)  # noqa: E731
    return draw(d, n_exp), draw(n_exp, d, f), draw(n_exp, d, f), draw(n_exp, f, d)


def _dropless_in_form(form, monkeypatch, *args, **kw):
    from deeplearning_mpi_tpu.models import moe

    monkeypatch.setattr(moe, "dropless_form", lambda *shape: form)
    return moe.dropless_moe(*args, **kw)


# (N, top_k, E) on both sides of the rule: claims under the experts, rows over the bound, and within both
FORM_SHAPES = [(16, 8, 64), (8, 8, 64), (4, 8, 64), (8, 8, 128), (4, 2, 8), (3, 2, 8), (96, 2, 8), (300, 2, 8), (1, 1, 4)]


@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "some-padding"])
@pytest.mark.parametrize("n_tok, top_k, n_exp", FORM_SHAPES)
def test_the_batched_and_the_grouped_form_agree_in_float32(n_tok, top_k, n_exp, masked, monkeypatch):
    """``y`` to accumulation order and ``touched`` exactly, whichever form
    the rule would pick at these shapes; a row that is not live yields zeros
    and claims nothing in both."""
    args = _dropless_args(n_exp)
    x = jnp.asarray(np.random.default_rng(n_tok).standard_normal((n_tok, 32)), jnp.float32)
    live = jnp.arange(n_tok) % 3 != 1 if masked else None
    with jax.default_matmul_precision("highest"):
        got = {
            form: _dropless_in_form(form, monkeypatch, x, *args, top_k=top_k, dtype=jnp.float32, live=live)
            for form in ("grouped", "batched")
        }
    (y_g, touched_g), (y_b, touched_b) = got["grouped"], got["batched"]
    scale = float(jnp.abs(y_g).max())
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_g), rtol=1e-5, atol=1e-5 * scale)
    assert int(touched_b) == int(touched_g) <= min(n_exp, n_tok * top_k)
    if masked:
        assert not np.asarray(y_b)[1::3].any() and not np.asarray(y_g)[1::3].any()


@pytest.mark.parametrize("product", ["gate", "up", "down"])
def test_an_unchosen_expert_that_overflows_stays_out_of_the_batched_sum(product, monkeypatch):
    """One expert's matrix is all inf and no row chooses it (its router
    logit is -1e4): the batched form computes it all the same, and masks it
    before the weighted sum, so ``y`` is bit for bit the sound layer's."""
    router, *mats = _dropless_args(8)
    bad = 5
    x = jnp.asarray(np.random.default_rng(5).standard_normal((6, 32)), jnp.float32).at[:, 0].set(1.0)
    router = router.at[:, bad].set(0.0).at[0, bad].set(-1e4)
    sound, touched = _dropless_in_form("batched", monkeypatch, x, router, *mats, top_k=2, dtype=jnp.float32)
    which = ["gate", "up", "down"].index(product)
    mats[which] = mats[which].at[bad].set(jnp.inf)
    y, touched_bad = _dropless_in_form("batched", monkeypatch, x, router, *mats, top_k=2, dtype=jnp.float32)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(sound))
    assert int(touched_bad) == int(touched) <= 7


@pytest.mark.parametrize("n_tok, top_k, n_exp, form", [
    (16, 8, 64, "batched"),     # mellum-serve-mixed's decode step: 128 claims over 64 experts
    (8, 8, 64, "batched"),      # the 8-row decode shape its lead may use
    (4, 8, 64, "grouped"),      # 32 claims: under the experts
    (1024, 8, 64, "grouped"),   # its prefill chunk
    (8, 8, 128, "grouped"),     # keye-serve-long's decode step: 64 claims over 128 experts
    (1, 8, 128, "grouped"),     # its probe
    (1024, 8, 128, "grouped"),  # its prefill chunk
    (4, 2, 8, "batched"),       # the toys of tests/test_mixed_serving.py: a decode table of 4 rows,
    (2, 2, 8, "grouped"),       # one of 2,
    (128, 2, 8, "batched"),     # and the keye-like toy's chunk
    (256, 8, 64, "batched"),    # the bound the chip gave (BATCHED_MAX_ROWS), on both sides
    (257, 8, 64, "grouped"),
])
def test_the_rule_picks_the_form_from_the_static_shapes(n_tok, top_k, n_exp, form):
    from deeplearning_mpi_tpu.models.moe import dropless_form

    assert dropless_form(n_tok, top_k, n_exp) == form


@pytest.mark.parametrize("n_tok, form", [(16, "batched"), (4, "grouped")])
def test_the_batched_form_traces_no_sort_and_no_grouped_product(n_tok, form):
    """The jaxpr under ``moe/`` of a layer over 16 rows (64 experts at top-8)
    holds no ``sort``, ``ragged_dot`` or ``cumsum``; the grouped form over 4
    rows holds the sorts and the three grouped products."""
    from deeplearning_mpi_tpu.models.moe import dropless_moe

    args = _dropless_args(64)
    text = str(jax.make_jaxpr(lambda x: dropless_moe(x, *args, top_k=8, dtype=jnp.float32))(jnp.zeros((n_tok, 32))))
    grouped_ops = set(re.findall(r"\b(sort|ragged_dot\w*|cumsum)\b", text))
    assert (not grouped_ops) == (form == "batched"), grouped_ops
    if form == "batched":
        assert text.count("dot_general") == 4  # the router and the three batched products
