"""Window and full attention layers side by side through the serving engine
(PR 36): two groups of layers, each with its own paged pool, allocator and
block table, the window group releasing what its window has left behind.
``ServingEngine`` and ``TransformerLM``'s uncached forward against the
benchmark's plain reference (``benchmark/mellum/reference.py``: float32, full
forward, no cache), at toy widths in float32: window 16 over blocks of 4, the
pattern window, window, window, full twice, YaRN on the full layers with an
original length of 32, 8 experts at top-2."""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.mellum import program, reference, weights
from deeplearning_mpi_tpu.models.transformer import (
    LayerSpec,
    TransformerConfig,
    TransformerLM,
    yarn_inv_freq,
)
from deeplearning_mpi_tpu.serving.engine import (
    EngineConfig,
    PagedForward,
    ServingEngine,
    _table_shapes,
    layer_groups,
    window_blocks,
    window_first_block,
)
from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers
from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

WINDOW, BS, CHUNK = 16, 4, 8
CFG = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 64, "num_hidden_layers": 8, "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2,
    "sliding_window": WINDOW,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 4, "original_max_position_embeddings": 32,
            "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "initializer_range": 0.02, "torch_dtype": "float32",
}
#: 24 blocks of 4 = 96 positions a sequence: six windows, three times YaRN's original length
ENGINE = EngineConfig(
    max_slots=4, block_size=BS, num_blocks=96, window_num_blocks=32, max_blocks_per_seq=24, prefill_chunk=CHUNK, max_queue=16,
)
MODEL = program.model_config(CFG)
RNG = np.random.default_rng(36)


@pytest.fixture(scope="module")
def params():
    return weights.build(CFG, weights.seed_words(36), jnp.float32)


def _prompt(n: int) -> np.ndarray:
    return RNG.integers(0, CFG["vocab_size"], n).astype(np.int32)


def _engine(params, model=MODEL, engine=ENGINE, **kw):
    return ServingEngine(model, params, engine, dtype=jnp.float32, **kw)


def _serve(engine, prompts, new):
    reqs = [engine.submit(p, new) for p in prompts]
    engine.run_until_idle()
    return [list(r.generated) for r in reqs]


def _reference_logits(params, prompt, served):
    """The reference's logits at the position that served each token."""
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    return np.asarray(reference.serve_logits(CFG, params, ids, np.arange(len(prompt) - 1, len(ids)), block=8))


# -- the model ---------------------------------------------------------------

def test_the_configuration_describes_each_layer():
    assert [spec.window for spec in MODEL.layers] == [16, 16, 16, 0] * 2
    assert [spec.yarn is not None for spec in MODEL.layers] == [False, False, False, True] * 2
    assert MODEL.layer_spec(3) == LayerSpec(0, 500000.0, (4.0, 32, 32.0, 1.0, 1.2772588722239782))
    one_kind = TransformerConfig.tiny()
    assert one_kind.layer_spec(1) == LayerSpec(one_kind.attention_window, one_kind.rope_theta)  # the global fields
    with pytest.raises(ValueError, match="layers describes 2 layers, num_layers is 8"):
        dataclasses.replace(MODEL, layers=MODEL.layers[:2])
    with pytest.raises(ValueError, match="attention_window beside layers"):
        dataclasses.replace(MODEL, attention_window=16)
    with pytest.raises(NotImplementedError, match="attention_topk > 0 with layers"):
        dataclasses.replace(MODEL, attention_topk=4)
    groups = layer_groups(MODEL)
    assert [(g.window, g.layers) for g in groups] == [(0, (3, 7)), (16, (0, 1, 2, 4, 5, 6))]
    assert [(g.window, g.layers) for g in layer_groups(one_kind)] == [(0, (0, 1))]
    three = dataclasses.replace(MODEL, layers=(LayerSpec(8),) + MODEL.layers[1:])
    with pytest.raises(NotImplementedError, match="window groups of several sizes"):
        layer_groups(three)


def test_the_architecture_sidecar_round_trips_the_layers(tmp_path):
    """``arch.json`` holds the layers as JSON holds them (lists); the same
    configuration matches its own sidecar, another window does not."""
    from deeplearning_mpi_tpu.utils.config import arch_mismatch_error, save_arch

    save_arch(MODEL, tmp_path)
    assert arch_mismatch_error(MODEL, tmp_path) is None
    assert arch_mismatch_error(TransformerConfig.tiny(), tmp_path) is not None
    other = dataclasses.replace(MODEL, layers=(LayerSpec(8, 500000.0),) + MODEL.layers[1:])
    assert "layers" in arch_mismatch_error(other, tmp_path)


def test_yarn_frequencies_at_the_published_numbers():
    """d = 128, theta = 500,000, original 8,192, factor 16, beta 32 and 1:
    corr(32) = 18.08, corr(1) = 34.98, so dimensions up to 18 keep their
    frequency, those from 35 on turn 16 times slower, a ramp between."""
    inv = yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    f = lambda j: 500000.0 ** (-j / 64)  # noqa: E731
    assert inv.shape == (64,) and inv.dtype == np.float32 and inv[0] == 1.0
    np.testing.assert_allclose(inv[:19], [f(j) for j in range(19)], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], [f(j) / 16 for j in range(35, 64)], rtol=1e-6)
    np.testing.assert_allclose(inv[26], f(26) * (1 - 8 / 17) + f(26) / 16 * (8 / 17), rtol=1e-6)
    np.testing.assert_allclose(inv[[18, 26, 35, 63]], [2.4955409e-02, 2.7043825e-03, 4.7781062e-05, 1.5344630e-07], rtol=1e-5)
    np.testing.assert_allclose(inv, reference.yarn(128, 500000.0, 16, 8192, 32, 1), rtol=1e-6)  # the reference's own


def test_the_uncached_forward_is_the_reference(params):
    ids = _prompt(96)
    ours = TransformerLM(MODEL, dtype=jnp.float32).apply({"params": params}, jnp.asarray(ids)[None])[0]
    want = reference.serve_logits(CFG, params, ids, np.arange(96), block=8)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), atol=2e-4)
    # and each of its parts matters there: a planted fault moves the logits
    for fault in reference.FAULTS:
        off = reference.serve_logits(CFG, params, ids, np.arange(96), block=8, faults=frozenset([fault]))
        assert np.abs(np.asarray(off) - np.asarray(want)).max() > 0.05, fault


# -- the engine against the reference ----------------------------------------

def test_served_tokens_and_last_chunk_logits_are_the_references(params):
    """Chunked prefill over several chunks, then decode past the window (16)
    and past YaRN's original length (32), short and long rows in one batch."""
    engine = _engine(params)
    last_logits = {}
    prefill = engine._prefill_fn

    def spy(*args):
        out = prefill(*args)
        last_logits[int(args[4]) + int(args[5])] = np.asarray(out[1])  # by the position the chunk ends at
        return out

    engine._prefill_fn = spy
    prompts = [_prompt(5), _prompt(21), _prompt(43), _prompt(60)]
    served = _serve(engine, prompts, 30)
    for prompt, tokens in zip(prompts, served):
        logits = _reference_logits(params, prompt, tokens)
        assert len(tokens) == 30 and check.served_gap(logits, tokens) < 1e-4
        np.testing.assert_allclose(last_logits[len(prompt)], logits[0], atol=2e-4)
    engine.pool.check()
    engine.window_pool.check()
    assert engine.pool.in_use == 0 and engine.window_pool.in_use == 0


def test_a_request_alone_and_among_strangers(params):
    prompt = _prompt(37)
    alone = _serve(_engine(params), [prompt], 24)[0]
    crowd = _serve(_engine(params), [_prompt(11), prompt, _prompt(52), _prompt(29)], 24)[1]
    assert alone == crowd


# -- the window group releases -----------------------------------------------

def test_the_window_group_holds_a_window_and_gives_back_the_rest(params):
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    pool = engine.window_pool
    reqs = [engine.submit(_prompt(n), 96 - n) for n in (9, 48)]
    most = {"prefill": window_blocks(WINDOW + CHUNK - 1, BS), "decode": window_blocks(WINDOW, BS)}
    free_at, seen = [], set()
    while not engine.scheduler.idle():
        engine.step()
        pool.check()
        engine.pool.check()
        for req in engine.scheduler.running():
            state = req.state.value
            assert len(req.window_blocks) <= most[state], (state, req.length)
            # the next query sits at position ``prefilled`` (a chunk's first) or ``length - 1`` (the decode step's)
            nxt = req.prefilled + 1 if state == "prefill" else req.length
            assert req.window_first == window_first_block(nxt, WINDOW, BS)  # everything behind it went back
            assert len(req.blocks) == engine.pool.blocks_for(max(req.prompt_len, req.length - 1))  # whole tables
            seen.update(req.window_blocks)
        if all(r.state.value == "decode" for r in reqs):
            free_at.append(pool.available)
    # both rows decode 48 and 87 positions: a whole table would grow by a block every 4 steps, the window's does not
    assert max(free_at) - min(free_at) <= 2 and min(free_at) >= pool.capacity - 2 * most["decode"]
    snap = registry.snapshot()
    released = int(snap["serve_window_released_blocks"])
    assert released == pool.total_freed - sum(len(r.window_blocks) for r in reqs) > 2 * (96 // BS - most["decode"] - 1)
    assert pool.total_allocated == pool.total_freed and pool.in_use == 0
    # every released block is allocatable again: the whole pool, and blocks were reused while the rows ran
    assert pool.total_allocated > len(seen) or len(seen) < 2 * 96 // BS
    assert sorted(pool.alloc(pool.capacity)) == list(range(1, ENGINE.window_num_blocks))


def test_the_window_pool_serves_prompts_many_times_its_size(params):
    """8 usable blocks of 4 = 32 positions in the window pool, one row of 90:
    without the release the prompt alone would need 23."""
    small = dataclasses.replace(ENGINE, window_num_blocks=9, max_slots=1)
    engine = _engine(params, engine=small)
    prompt = _prompt(90)
    tokens = _serve(engine, [prompt], 6)[0]
    assert check.served_gap(_reference_logits(params, prompt, tokens), tokens) < 1e-4
    assert engine.window_pool.total_allocated >= 23 and engine.scheduler.evicted_count == 0


def test_window_pool_pressure_evicts_the_oldest_and_keeps_the_books(params):
    tight = dataclasses.replace(ENGINE, window_num_blocks=12, max_slots=3)  # 11 usable: two rows' chunks, not three
    engine = _engine(params, engine=tight)
    reqs = [engine.submit(_prompt(40), 8) for _ in range(3)]
    engine.run_until_idle()
    engine.pool.check()
    engine.window_pool.check()
    assert engine.pool.in_use == 0 and engine.window_pool.in_use == 0
    done = [r for r in reqs if r.state.value == "finished"]
    assert done and all(len(r.generated) == 8 for r in done)
    assert {r.shed_reason for r in reqs if r.state.value == "shed"} <= {"evicted"}


def test_recovery_rebuilds_both_pools(params):
    engine = _engine(params)
    reqs = [engine.submit(_prompt(30), 10) for _ in range(2)]
    for _ in range(3):
        engine.step()
    assert engine.window_pool.in_use > 0
    engine.recover()
    assert engine.window_pool.in_use == 0 and engine.pool.in_use == 0
    engine.run_until_idle()
    assert all(r.state.value == "finished" and len(r.generated) == 10 for r in reqs)


# -- static shapes -----------------------------------------------------------

def test_zero_compiles_after_warmup_while_rows_cross_the_window(params):
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    programs = engine.warmup()
    widths, shapes = _table_shapes(ENGINE.max_slots, ENGINE.max_blocks_per_seq)
    # the 1- and 2-row buckets would take the grouped form of the expert layer where 4 rows are batched: not built (PR 37)
    assert set(engine._decode_shapes) == {s for s in shapes if s[0] == ENGINE.max_slots} and len(engine._decode_shapes) == 4
    assert len(programs) == len(engine._decode_shapes) + len(widths)  # the window group's one width multiplies nothing
    assert engine._window_widths == (window_blocks(WINDOW, BS), window_blocks(WINDOW + CHUNK - 1, BS)) == (5, 7)
    compiled = registry.snapshot()["serve_compile_total"]
    served = _serve(engine, [_prompt(3), _prompt(14), _prompt(33), _prompt(50)], 40)  # 3 -> 43: across the window
    assert all(len(t) == 40 for t in served)
    assert registry.snapshot()["serve_compile_total"] == compiled
    assert engine._decode_fn.fallback_calls == 0 and engine._prefill_fn.fallback_calls == 0


def test_the_launch_spans_and_counters_count_both_groups(params, monkeypatch):
    from deeplearning_mpi_tpu.serving import engine as engine_mod

    spans = []

    class Span:
        def __init__(self, name, **labels):
            self.name, self.labels = name, dict(labels)
            spans.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **labels):
            self.labels.update(labels)

    monkeypatch.setattr(engine_mod, "span", Span)
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    _serve(engine, [_prompt(30), _prompt(7)], 20)
    decode = [s.labels for s in spans if s.name == "serve/decode_launch"]
    prefill = [s.labels for s in spans if s.name == "serve/prefill_launch"]
    assert all({"rows", "table_rows", "width", "skipped", "topk", "live", "window_width", "window_live", "released", "moe"} == set(d) for d in decode)
    assert all({"rid", "start", "n", "width", "topk", "window_width", "window_live", "released", "moe"} == set(p) for p in prefill)
    assert {d["window_width"] for d in decode} == {5} and {p["window_width"] for p in prefill} == {7}
    assert all(d["skipped"] == 0 and d["window_live"] <= d["rows"] * 5 for d in decode)
    snap = registry.snapshot()
    assert snap["serve_window_released_blocks"] == sum(s["released"] for s in decode + prefill) > 0
    assert snap["serve_gather_blocks"] == sum(d["table_rows"] * (d["width"] + 5) for d in decode) + sum(p["width"] + 7 for p in prefill)
    assert snap["serve_live_blocks"] >= sum(d["live"] + d["window_live"] for d in decode)
    assert snap["serve_window_skipped_blocks"] == 0


def test_a_launch_hands_over_both_groups_tables_in_one_transfer_span(params, monkeypatch):
    """Inside every launch of a two-group model (``serving/launch.py``):
    ``launch/prep``, one ``launch/h2d`` whose ``bytes`` hold the window
    group's table beside the full group's, and one ``launch/dispatch`` of a
    warmed executable; inside every fetch ``fetch/ready`` then ``fetch/d2h``
    (the tokens and the touched-experts count)."""
    from deeplearning_mpi_tpu.serving import engine as engine_mod
    from deeplearning_mpi_tpu.serving import launch as launch_mod

    spans = []

    class Span:
        def __init__(self, name, **labels):
            self.name, self.labels = name, dict(labels)
            spans.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **labels):
            self.labels.update(labels)

    monkeypatch.setattr(engine_mod, "span", Span)
    monkeypatch.setattr(launch_mod, "span", Span)
    engine = _engine(params)
    programs = set(engine.warmup())
    _serve(engine, [_prompt(30), _prompt(7)], 6)
    names = [s.name for s in spans]
    launches = [i for i, n in enumerate(names) if n in ("serve/decode_launch", "serve/prefill_launch")]
    assert launches and {names[i] for i in launches} == {"serve/decode_launch", "serve/prefill_launch"}
    for i in launches:
        launch, prep, h2d, dispatch = spans[i : i + 4]
        assert (prep.name, h2d.name, dispatch.name) == ("launch/prep", "launch/h2d", "launch/dispatch")
        assert dispatch.labels["fallback"] == 0 and dispatch.labels["program"] in programs
        ww = launch.labels["window_width"]
        if launch.name == "serve/decode_launch":  # int32 tables, lengths, tokens; bool active
            rows, width = launch.labels["table_rows"], launch.labels["width"]
            assert h2d.labels["bytes"] == 4 * rows * (width + ww + 2) + rows
        else:  # int32 tables, chunk, start, n_valid
            assert h2d.labels["bytes"] == 4 * (launch.labels["width"] + ww + CHUNK + 2)
    fetches = [i for i, n in enumerate(names) if n in ("serve/token_fetch", "serve/first_token_fetch")]
    assert fetches and all(names[i + 1 : i + 3] == ["fetch/ready", "fetch/d2h"] for i in fetches)
    rows = {i: spans[i].labels["table_rows"] for i in launches if names[i] == "serve/decode_launch"}
    for i in fetches:  # the tokens; a decode step's touched-experts count per layer beside them
        if names[i] == "serve/token_fetch":
            decode = max(j for j in rows if j < i)
            assert spans[i + 2].labels["bytes"] == 4 * rows[decode] + 4 * engine.config.num_layers
        else:
            assert spans[i + 2].labels["bytes"] == 4


@pytest.mark.parametrize("slots, form", [(4, "batched"), (2, "grouped")], ids=["four-slots", "two-slots"])
def test_the_launch_spans_say_which_form_the_expert_layer_took(params, monkeypatch, slots, form):
    """``moe`` on every launch is the form the launched program HAS (its
    jaxpr holds ``ragged_dot`` iff grouped), and the engine builds its
    decode programs in one form, that of ``max_slots`` rows (8 experts at
    top-2: 4 rows are batched, so their 1- and 2-row buckets are not built;
    2 rows are grouped), so one row and four decode alike."""
    from deeplearning_mpi_tpu.models.moe import dropless_form
    from deeplearning_mpi_tpu.serving import engine as engine_mod

    spans = []
    real = engine_mod.span

    def spy(name, **labels):
        spans.append((name, labels))
        return real(name, **labels)

    monkeypatch.setattr(engine_mod, "span", spy)
    registry = MetricsRegistry()
    engine = _engine(params, engine=dataclasses.replace(ENGINE, max_slots=slots), registry=registry)
    assert {dropless_form(rows, 2, 8) for rows, _ in engine._decode_shapes} == {form}
    assert {rows for rows, _ in engine._decode_shapes} == ({4} if slots == 4 else {1, 2})
    _serve(engine, [_prompt(30)], 6)  # one row decodes, then up to ``slots``
    _serve(engine, [_prompt(n) for n in (30, 7, 19, 41)[:slots]], 12)
    decode = [labels for name, labels in spans if name == "serve/decode_launch"]
    prefill = [labels for name, labels in spans if name == "serve/prefill_launch"]
    assert {d["rows"] for d in decode} >= {1, slots} and {d["moe"] for d in decode} == {form}
    assert {p["moe"] for p in prefill} == {"batched"} == {dropless_form(CHUNK, 2, 8)}

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    for rows, width, window_width in {(d["table_rows"], d["width"], d["window_width"]) for d in decode}:
        text = str(jax.make_jaxpr(engine._fwd.decode_step)(
            engine.params, engine._kv, (i32(rows, width), i32(rows, window_width)), i32(rows), i32(rows),
            jax.ShapeDtypeStruct((rows,), jnp.bool_),
        ))
        assert ("ragged_dot" in text) == (form == "grouped")
    for width, window_width in {(p["width"], p["window_width"]) for p in prefill}:
        text = str(jax.make_jaxpr(engine._fwd.prefill_chunk)(
            engine.params, engine._kv, (i32(width), i32(window_width)), i32(CHUNK), i32(), i32(),
        ))
        assert "ragged_dot" not in text
    assert registry.snapshot()["serve_decode_steps"] == len(decode)


# -- what is refused, by name ------------------------------------------------

@pytest.mark.parametrize("change, error, match", [
    ({"prefix_cache": True}, NotImplementedError, "the prefix cache"),
    ({"spec_k": 2}, NotImplementedError, "spec_k > 0"),
    ({"kv_dtype": "int8"}, NotImplementedError, "kv_dtype='int8'"),
    ({"max_blocks_per_seq": 4, "num_blocks": 8}, NotImplementedError, "a window of 16 that cannot bind within max_seq_len 16"),
    ({"window_num_blocks": 0}, ValueError, "window pool capacity"),
    ({"window_num_blocks": 7}, ValueError, "below the 7 blocks one prefill chunk of 8 under a window of 16 can reach"),
])
def test_what_two_groups_do_not_serve_is_refused_by_name(params, change, error, match):
    kw = {}
    if "spec_k" in change:
        kw = {"draft_config": TransformerConfig.tiny(), "draft_params": params}
    with pytest.raises(error, match=match):
        _engine(params, engine=dataclasses.replace(ENGINE, **change), **kw)


def test_a_disaggregated_hand_off_and_a_stray_window_pool_are_refused(params):
    from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

    with pytest.raises(NotImplementedError, match="a disaggregated hand-off"):
        _engine(params, pool=PagedKVPool(ENGINE.num_blocks, BS))
    with pytest.raises(NotImplementedError, match="a disaggregated hand-off"):
        _engine(params, role="prefill")
    one_kind = dataclasses.replace(MODEL, layers=())
    with pytest.raises(ValueError, match="this model's layers are of one kind"):
        _engine(params, model=one_kind)


# -- one-kind models run the programs they ran -------------------------------

#: sha256[:16] of ``str(jax.make_jaxpr(program)(shapes))`` on the PARENT of PR 36 (commit 261fd64), from this file's own
#: ``_program_digests``: a model whose layers are of one kind is one group and traces the programs it traced before
#: the groups existed. A PR that changes those programs on purpose reads the new digests off the failure and says so.
PARENT_PROGRAMS = {
    "mistral-like": {
        "decode@2x5": "c3d662ac1f569e9a", "decode@4x1": "c489b38c929bc587", "decode@4x5": "b30861db91ab2a51",
        "prefill@2": "3de8a2a326dedae4", "prefill@16": "6cc761183f1a6f1f", "verify": "0d07bed6ff01fe8b",
    },
    "mistral-like-unbound": {"decode@4x16": "c5be8de70bfea2f6", "decode@2x8": "14a6ee5fec2b6815", "prefill@8": "295f5130b01b5aab"},
    "keye-like": {
        # four of the five moved on purpose in PR 37: 4 rows x top-2 = 8 claims over 8 experts crosses
        # models/moe.py:dropless_form's rule, and so does the chunk of 128 (256 claims, under BATCHED_MAX_ROWS = 256
        # rows, the bound the chip gave), so their expert layers are batched products; 1 row is the parent's. The
        # REAL sizes' programs (keye-serve-long: 8 rows x top-8 over 128 experts, chunks of 1,024) are the parent's
        # by text: PERF.md section 6, PR 37
        "decode@4x16": "d7e1c1413992ebf4", "decode@1x8": "14a23ef253408e9f", "decode@4x2": "6db05cfe0b5e5498",
        "prefill@4": "d40d22133fc4cb46", "prefill@16": "0b3a3f28c993a0ba",
    },
    # a two-group model (window and full layers, dropless experts), taken on commit 5e200f3, before a model with
    # latent attention and an expert share existed: the 4-row programs' expert layer is batched, the 1- and 2-row ones'
    # grouped
    "mellum-like": {
        "decode@4x16": "de32bbce6ccd66d4", "decode@2x8": "9296bb8ff1519f79", "decode@1x16": "507663ff9b093ceb",
        "prefill@2": "8f33235c81ab55c6", "prefill@16": "bbb5c7ce8775bab8",
    },
}
_TINY = dict(vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, d_model=32, d_ff=64, tied_embeddings=False)
_SMALL = dict(max_slots=4, block_size=4, num_blocks=64, max_blocks_per_seq=16)
ONE_KIND = {
    "mistral-like": (TransformerConfig(**_TINY, attention_window=16), EngineConfig(**_SMALL, prefill_chunk=8), 2),
    "mistral-like-unbound": (TransformerConfig(**_TINY, attention_window=128), EngineConfig(**_SMALL, prefill_chunk=8), 0),
    "keye-like": (
        TransformerConfig(
            **_TINY, rope_theta=1e6, qk_norm=True, moe_experts=8, moe_top_k=2, moe_routing="dropless", moe_d_ff=24,
            attention_topk=8, indexer_heads=2, indexer_head_dim=8,
        ),
        EngineConfig(**_SMALL, prefill_chunk=128), 0,
    ),
    "mellum-like": (
        TransformerConfig(
            **_TINY, moe_experts=8, moe_top_k=2, moe_routing="dropless", moe_d_ff=24,
            layers=(LayerSpec(16, 500000.0), LayerSpec(0, 500000.0, (4.0, 32, 32.0, 1.0, 1.2772588722239782))),
        ),
        EngineConfig(**_SMALL, window_num_blocks=16, prefill_chunk=8), 0,
    ),
}


def _program_digests(cfg: TransformerConfig, eng: EngineConfig, spec_k: int) -> dict[str, str]:
    fwd = PagedForward(cfg, eng, jnp.float32, window_cut=True)
    model = TransformerLM(cfg, dtype=jnp.float32)
    weights_ = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    kv = jax.eval_shape(lambda: tuple(
        init_kv_buffers(
            len(group.layers), blocks, eng.block_size, cfg.num_kv_heads, cfg.head_dim, jnp.float32,
            index_dim=cfg.indexer_head_dim if cfg.attention_topk else 0,
        )
        for group, blocks in zip(layer_groups(cfg), (eng.num_blocks, eng.window_num_blocks))
    ))
    kv = kv if fwd.mixed else kv[0]
    cut = fwd.decode_window and not fwd.mixed  # a two-group model's ladder is on the full group's whole tables
    reach = min(eng.max_blocks_per_seq, window_blocks(fwd.decode_window, eng.block_size)) if cut else eng.max_blocks_per_seq
    widths, shapes = _table_shapes(eng.max_slots, eng.max_blocks_per_seq, reach)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    flags = lambda n: jax.ShapeDtypeStruct((n,), jnp.bool_)  # noqa: E731
    # the window group's table of a two-group model: ONE width a program kind (ServingEngine._window_widths)
    reach_of = lambda n: min(window_blocks(n, eng.block_size), eng.max_blocks_per_seq)  # noqa: E731
    window = (reach_of(fwd.decode_window), reach_of(fwd.decode_window + eng.prefill_chunk - 1)) if fwd.mixed else None
    table = lambda *s, kind: (i32(*s), i32(*s[:-1], window[kind])) if window else i32(*s)  # noqa: E731
    calls = {f"decode@{r}x{w}": (fwd.decode_step, (weights_, kv, table(r, w, kind=0), i32(r), i32(r), flags(r))) for r, w in shapes}
    calls |= {f"prefill@{w}": (fwd.prefill_chunk, (weights_, kv, table(w, kind=1), i32(eng.prefill_chunk), i32(), i32())) for w in widths}
    if spec_k:
        s = eng.max_slots
        calls["verify"] = (fwd.verify_step, (weights_, kv, i32(s, eng.max_blocks_per_seq), i32(s), i32(s, spec_k + 1), i32(s), flags(s)))
    return {name: hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16] for name, (fn, args) in calls.items()}


@pytest.mark.parametrize("name", list(ONE_KIND))
def test_a_one_kind_model_traces_the_parents_programs(name):
    got = _program_digests(*ONE_KIND[name])
    assert {k: got[k] for k in PARENT_PROGRAMS[name]} == PARENT_PROGRAMS[name]
