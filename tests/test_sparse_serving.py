"""Learned sparse attention and the dropless expert layer through the serving
engine (PR 34): ``PagedForward``/``ServingEngine`` against the benchmark's
plain reference (``benchmark/keye/reference.py``: float32, full forward, no
cache) and against ``TransformerLM``'s own uncached forward, at toy widths in
float32 with a top-k small enough to bind."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.keye import program, reference, weights
from deeplearning_mpi_tpu.models.moe import dropless_moe
from deeplearning_mpi_tpu.models.transformer import TransformerConfig, TransformerLM
from deeplearning_mpi_tpu.serving.engine import EngineConfig, PagedForward, ServingEngine
from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers
from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

CFG = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 64, "num_hidden_layers": 2, "tie_word_embeddings": False, "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "topk": 8}, "initializer_range": 0.02, "torch_dtype": "float32",
}
#: 16 blocks of 4 = 64 positions a sequence: a table of 4 blocks or more is wider than the top-k of 8
ENGINE = EngineConfig(max_slots=4, block_size=4, num_blocks=96, max_blocks_per_seq=16, prefill_chunk=8, max_queue=16)
MODEL = program.model_config(CFG)
RNG = np.random.default_rng(34)


@pytest.fixture(scope="module")
def params():
    return weights.build(CFG, weights.seed_words(34), jnp.float32)


def _prompt(n: int) -> np.ndarray:
    return RNG.integers(0, CFG["vocab_size"], n).astype(np.int32)


def _engine(params, model=MODEL, engine=ENGINE, **kw):
    return ServingEngine(model, params, engine, dtype=jnp.float32, **kw)


def _serve(engine, prompts, new):
    reqs = [engine.submit(p, new) for p in prompts]
    engine.run_until_idle()
    return [list(r.generated) for r in reqs]


def _reference_gap(params, prompt, served) -> float:
    """How far below the reference's best the served tokens' logits lie."""
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(ids))
    return check.served_gap(np.asarray(reference.serve_logits(CFG, params, ids, rows)), served)


# (e) the flax forward is the model the reference describes
def test_uncached_forward_equals_the_reference(params):
    ids = _prompt(40)
    with jax.default_matmul_precision("highest"):
        got = TransformerLM(MODEL, dtype=jnp.float32).apply({"params": params}, jnp.asarray(ids)[None])[0]
    want = reference.serve_logits(CFG, params, ids, np.arange(40))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_init_yields_the_tree_the_benchmark_builds(params):
    made = jax.eval_shape(lambda: TransformerLM(MODEL, dtype=jnp.float32).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.structure(made) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(made)] == [a.shape for a in jax.tree.leaves(params)]


# (a) prefill in several chunks, then rows of unequal length in one decode step
def test_paged_forward_matches_the_reference(params):
    fwd = PagedForward(MODEL, ENGINE, jnp.float32)
    kv = init_kv_buffers(2, ENGINE.num_blocks, 4, 2, 8, jnp.float32, index_dim=8)
    prefill, decode = jax.jit(fwd.prefill_chunk), jax.jit(fwd.decode_step)
    prompts = [_prompt(29), _prompt(13)]
    tables = np.zeros((2, 16), np.int32)
    tables[0], tables[1] = np.arange(1, 17), np.arange(17, 33)
    last = []
    for table, prompt in zip(tables, prompts):
        for start in range(0, len(prompt), 8):
            n = min(8, len(prompt) - start)
            chunk = np.zeros(8, np.int32)
            chunk[:n] = prompt[start : start + n]
            kv, logits = prefill(params, kv, jnp.asarray(table), jnp.asarray(chunk), jnp.int32(start), jnp.int32(n))
        want = reference.serve_logits(CFG, params, prompt, np.array([len(prompt) - 1]))[0]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-5)
        last.append(int(jnp.argmax(logits)))
    served = [[t] for t in last]
    for _ in range(12):
        lengths = np.array([len(p) + len(s) for p, s in zip(prompts, served)], np.int32)
        kv, out, touched = decode(params, kv, jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray([s[-1] for s in served], jnp.int32), jnp.ones(2, bool))
        assert out.shape == (2,) and touched.shape == (2,)  # a token a row; the experts each layer touched
        assert ((np.asarray(touched) >= 2) & (np.asarray(touched) <= 4)).all()  # two rows x top-2
        for s, t in zip(served, np.asarray(out)):
            s.append(int(t))
    for prompt, s in zip(prompts, served):
        assert _reference_gap(params, prompt, s) <= 1e-4


# (b) while a query sees no more keys than the top-k, selection is the dense layer
@pytest.mark.parametrize("lengths", [(5, 7), (21, 30)], ids=["narrow-table", "wide-table"])
def test_contexts_under_the_topk_equal_the_dense_layer(params, lengths):
    """A table of at most ``attention_topk`` positions takes the dense path;
    a wider one selects, and keeps every key while the contexts are shorter
    than the top-k (here 40 > 30 + 6)."""
    wide = dataclasses.replace(MODEL, attention_topk=40)
    dense = dataclasses.replace(MODEL, attention_topk=0, indexer_heads=0, indexer_head_dim=0)
    prompts = [_prompt(n) for n in lengths]
    assert _serve(_engine(params, wide), prompts, 6) == _serve(_engine(params, dense), prompts, 6)


def test_a_binding_topk_differs_from_the_dense_layer(params):
    dense = dataclasses.replace(MODEL, attention_topk=0, indexer_heads=0, indexer_head_dim=0)
    prompts = [_prompt(40)]
    assert _serve(_engine(params), prompts, 12) != _serve(_engine(params, dense), prompts, 12)


# (c) request independence
def test_alone_and_among_strangers_the_stream_is_the_same(params):
    mine, strangers = _prompt(33), [_prompt(n) for n in (17, 26, 41)]
    alone = _serve(_engine(params), [mine], 16)[0]
    among = _serve(_engine(params), [strangers[0], mine, *strangers[1:]], 16)[1]
    assert alone == among
    assert _reference_gap(params, mine, alone) <= 1e-4


@pytest.mark.parametrize("slots, top_k, experts, rows", [
    (4, 2, 8, {4}),           # this file's engine: 1 and 2 rows would be grouped, 4 are batched
    (16, 8, 64, {8, 16}),     # mellum-serve-mixed: the 4-row bucket (32 claims over 64) is not built
    (8, 8, 128, {2, 4, 8}),   # keye-serve-long: grouped at every bucket, nothing goes
    (2, 2, 8, {1, 2}),
    (512, 8, 64, {512}),      # over BATCHED_MAX_ROWS the step is grouped: its 128- and 256-row buckets go
], ids=["toy", "mellum", "keye", "two-slots", "many-slots"])
def test_every_decode_program_of_an_engine_takes_one_form_of_the_expert_layer(slots, top_k, experts, rows):
    """The two forms round differently, so how many strangers share a step
    must not pick the form: the engine keeps the row buckets that take the
    form of ``max_slots`` rows, at every width it had them."""
    from deeplearning_mpi_tpu.models.moe import dropless_form
    from deeplearning_mpi_tpu.serving.engine import _table_shapes

    def abstract(model):
        return jax.eval_shape(lambda: TransformerLM(model, dtype=jnp.float32).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    model = dataclasses.replace(MODEL, moe_experts=experts, moe_top_k=top_k)
    engine = _engine(abstract(model), model, dataclasses.replace(ENGINE, max_slots=slots, num_blocks=slots * 16 + 1))
    built = engine._decode_shapes
    assert {r for r, _ in built} == rows
    assert len({dropless_form(r, top_k, experts) for r, _ in built}) == 1
    assert set(built) == {s for s in _table_shapes(slots, ENGINE.max_blocks_per_seq)[1] if s[0] in rows}
    dense = dataclasses.replace(MODEL, moe_experts=0, attention_topk=0, indexer_heads=0, indexer_head_dim=0)
    assert _engine(abstract(dense), dense)._decode_shapes == _table_shapes(ENGINE.max_slots, ENGINE.max_blocks_per_seq)[1]


def test_the_dropless_layer_serves_a_token_by_itself(params):
    mlp = params["layer_0"]["mlp"]
    x = jnp.asarray(RNG.standard_normal((7, 32)), jnp.float32)
    args = (mlp["router"]["kernel"], mlp["experts_gate"], mlp["experts_up"], mlp["experts_down"])
    serve = lambda rows, live=None: dropless_moe(rows, *args, top_k=2, dtype=jnp.float32, live=live)  # noqa: E731
    # bit for bit inside each form of the three products (models/moe.py:dropless_form): grouped under 8 claims (2 rows and
    # 3: XLA:CPU rounds the router's product of ONE row, a matrix-vector product, in another order than any other count) ...
    np.testing.assert_array_equal(np.asarray(serve(x[:2])[0]), np.asarray(serve(x[:3])[0][:2]))
    # ... and batched from 4 rows on: alone in the table (the engine's padding beside it), among five, among seven
    alone, one = serve(x[:5], jnp.arange(5) < 1)
    among, touched = serve(x[:5])
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(among[0]))
    np.testing.assert_array_equal(np.asarray(among), np.asarray(serve(x)[0][:5]))
    assert not np.asarray(alone[1:]).any() and int(one) == 2
    # across the forms the same sums run in another order: the engine builds its decode programs in ONE form
    np.testing.assert_allclose(np.asarray(among[0]), np.asarray(serve(x[:1])[0][0]), rtol=1e-5, atol=1e-6)
    padded, fewer = serve(x[:5], jnp.arange(5) < 2)
    np.testing.assert_array_equal(np.asarray(padded[:2]), np.asarray(among[:2]))
    assert not np.asarray(padded[2:]).any() and int(fewer) <= 4 <= int(touched) + 2


def test_the_dropless_layer_equals_the_reference_and_counts_its_experts(params):
    lp = params["layer_1"]
    h = jnp.asarray(RNG.standard_normal((24, 32)), jnp.float32)
    mlp = lp["mlp"]
    with jax.default_matmul_precision("highest"):
        got, touched = dropless_moe(h, mlp["router"]["kernel"], mlp["experts_gate"], mlp["experts_up"], mlp["experts_down"], top_k=2, dtype=jnp.float32)
    gates = reference._route(h, lp, CFG, lambda a: a, frozenset())
    want = reference._experts(jnp.zeros_like(h), h, gates, mlp, 24, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(touched) == int((np.asarray(gates) > 0).any(axis=0).sum())


# (d) the third pool travels with K and V
def test_copy_block_carries_the_indexer_keys():
    fwd = PagedForward(MODEL, ENGINE, jnp.float32)
    kv = tuple(jnp.asarray(RNG.standard_normal(b.shape), jnp.float32) for b in init_kv_buffers(2, 8, 4, 2, 8, jnp.float32, index_dim=8))
    assert [b.shape for b in kv] == [(2, 8, 4, 2, 8), (2, 8, 4, 2, 8), (2, 8, 4, 8)]
    out = fwd.copy_block(kv, jnp.int32(3), jnp.int32(5))
    for before, after in zip(kv, out):
        np.testing.assert_array_equal(np.asarray(after[:, 5]), np.asarray(before[:, 3]))
        np.testing.assert_array_equal(np.asarray(after[:, :5]), np.asarray(before[:, :5]))


def test_a_prefix_cache_hit_carries_the_indexer_keys(params):
    shared = _prompt(26)  # six whole blocks and a partial one: adoption and a copy-on-write
    first, second = np.concatenate([shared, _prompt(9)]), np.concatenate([shared, _prompt(14)])
    cold = [_serve(_engine(params), [p], 10)[0] for p in (first, second)]
    registry = MetricsRegistry()
    warm = _engine(params, engine=dataclasses.replace(ENGINE, prefix_cache=True), registry=registry)
    assert [_serve(warm, [p], 10)[0] for p in (first, second)] == cold
    snap = registry.snapshot()
    assert snap["serve_prefix_tokens_reused_total"] >= 24 and snap["serve_prefix_cow_copies_total"] >= 1


def test_the_pools_bytes_count_the_indexer_keys(params):
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    engine.step()
    per_position = 2 * (2 * 2 * 8 + 8) * 4  # layers x (K and V of 2 heads x 8, 8 indexer dims) x float32
    assert registry.snapshot()["serve_kv_bytes"] == ENGINE.num_blocks * 4 * per_position


# (f) what is refused, by name
def test_selection_refuses_the_verify_step(params):
    with pytest.raises(NotImplementedError, match="spec_k"):
        _engine(params, engine=dataclasses.replace(ENGINE, spec_k=2), draft_config=MODEL, draft_params=params)


def test_a_dropless_model_without_selection_serves_under_speculation(params):
    """Only selection refuses ``spec_k``: a dropless target verifies through
    the same expert layer (the draft has to be dense, and reads the tokens
    of its own ``decode_step`` beside the empty touched-experts output)."""
    model = dataclasses.replace(MODEL, attention_topk=0, indexer_heads=0, indexer_head_dim=0)
    small = dataclasses.replace(model, num_layers=1, moe_experts=0, d_ff=32)
    draft = TransformerLM(small, dtype=jnp.float32).init(jax.random.key(3), jnp.zeros((1, 4), jnp.int32))["params"]
    prompts = [_prompt(21), _prompt(9)]
    plain = _serve(_engine(params, model), prompts, 10)
    registry = MetricsRegistry()
    spec = _engine(params, model, dataclasses.replace(ENGINE, spec_k=2), draft_config=small, draft_params=draft, registry=registry)
    assert _serve(spec, prompts, 10) == plain
    assert registry.snapshot()["spec_proposed_total"] > 0


def test_selection_refuses_an_integer_pool(params):
    with pytest.raises(NotImplementedError, match="kv_dtype"):
        _engine(params, engine=dataclasses.replace(ENGINE, kv_dtype="int8"))


def test_capacity_routing_is_still_refused():
    with pytest.raises(NotImplementedError, match="capacity routing"):
        ServingEngine(TransformerConfig.tiny_moe(), {}, EngineConfig())


@pytest.mark.parametrize("decode", [True, "prefill"])
def test_the_cached_flax_paths_refuse_selection(params, decode):
    with pytest.raises(NotImplementedError, match="attention_topk"):
        TransformerLM(MODEL, dtype=jnp.float32, decode=decode).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# (g) the counters are what the reference counts
def test_counters_equal_what_the_reference_counts(params):
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    prompt, new = _prompt(19), 9
    served = _serve(engine, [prompt], new)[0]
    snap = registry.snapshot()
    steps = new - 1  # the first token comes from the prefill
    lengths = [len(prompt) + j for j in range(1, new)]
    assert snap["serve_decode_steps"] == steps
    assert snap["serve_select_live_keys"] == sum(lengths)
    assert snap["serve_select_kept_keys"] == sum(min(n, 8) for n in lengths)
    assert snap["serve_moe_expert_slots"] == steps * 2 * 8
    # one row a step: each layer touches exactly the token's two experts
    assert snap["serve_moe_experts_touched"] == steps * 2 * 2
    assert _reference_gap(params, prompt, served) <= 1e-4


# the decode step reads rows, never the table's K/V pages; and it syncs once
def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _kv_gathers(program, params, kv, *args):
    """Output shapes of every gather from a pool, by which pool."""
    shapes = [b.shape for b in kv]
    found = {0: [], 1: [], 2: []}
    for eqn in _equations(jax.make_jaxpr(program)(params, kv, *args).jaxpr):
        if eqn.primitive.name == "gather" and eqn.invars[0].aval.shape in shapes:
            for which in (i for i, s in enumerate(shapes) if s == eqn.invars[0].aval.shape):
                found[which].append(eqn.outvars[0].aval.shape)
    return found


def test_the_decode_step_gathers_rows_not_pages(params):
    kv = init_kv_buffers(2, ENGINE.num_blocks, 4, 2, 8, jnp.float32, index_dim=12)  # a width of its own, so the pools differ in shape
    model = dataclasses.replace(MODEL, indexer_head_dim=12)
    tree = jax.eval_shape(lambda: TransformerLM(model, dtype=jnp.float32).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    fwd = PagedForward(model, ENGINE, jnp.float32)
    z = lambda *s, dtype=jnp.int32: jnp.zeros(s, dtype)  # noqa: E731
    k_pool = [b.shape for b in kv][0]
    # K and V share a shape: every gather of either is found under both
    decode = _kv_gathers(fwd.decode_step, tree, kv, z(4, 16), z(4), z(4), z(4, dtype=bool))
    assert len(decode[0]) == 2 * 2 and len(decode[2]) == 2  # K and V a layer; the indexer keys a layer
    for shape in decode[0]:
        assert shape == (4, 1, 8, 2, 8), shape  # rows x 1 query x top-k kept rows x [Hkv, D]: no block_size axis
    assert all(shape == (4, 16, 4, 12) for shape in decode[2])  # the small pool's pages
    # a narrow table (16 positions > top-k 8 still selects; 8 positions do not): whole pages, the dense layer
    dense = _kv_gathers(fwd.decode_step, tree, kv, z(4, 2), z(4), z(4), z(4, dtype=bool))
    assert all(shape == (4, 2) + k_pool[2:] for shape in dense[0]) and not dense[2]
    # the prefill chunk's 8 queries x top-k 8 are more rows than a table of 32 positions holds:
    # it reads the table's pages once for all its queries
    chunk = _kv_gathers(fwd.prefill_chunk, tree, kv, z(8), z(8), jnp.int32(0), jnp.int32(8))
    assert all(shape == (1, 8) + k_pool[2:] for shape in chunk[0]) and len(chunk[0]) == 2 * 2


def test_the_token_fetch_is_the_decode_steps_only_sync(params, monkeypatch):
    engine = _engine(params, registry=MetricsRegistry())
    engine.submit(_prompt(30), 6)
    while not any(r.state.value == "decode" for r in engine.scheduler.running()):
        engine.step()
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    engine.step()
    assert len(calls) == 1
