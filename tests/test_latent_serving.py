"""Multi-head latent attention, a leading dense layer and a share of a sigmoid
router's experts through the serving engine: Kimi-K2.7-Code's kinds of layer
(``benchmark/configs/kimi-k2.7-code-l5.json``) at toy widths in float32.
``ServingEngine`` and ``TransformerLM``'s uncached forward against the
benchmark's plain reference (``benchmark/kimi/reference.py``: float32, the
expanded attention written out per head, full forward, no cache): 3 layers
(one dense, two of experts), 4 heads of 8 + 8 with values of 12, a latent of
16 + 8, experts 4..7 held of a router of 16 at top-4 beside a shared expert,
YaRN over an original length of 32."""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.kimi import program, reference, weights
from benchmark.manifest import ROOT
from deeplearning_mpi_tpu.models.moe import Routing, dropless_form, dropless_moe
from deeplearning_mpi_tpu.models.transformer import (
    LayerSpec,
    RMSNorm,
    TransformerConfig,
    TransformerLM,
    yarn_inv_freq,
)
from deeplearning_mpi_tpu.compiler import aot
from deeplearning_mpi_tpu.ops.latent_attention import (
    absorbed_attention,
    chunk_attention,
    expanded_attention,
    paged_absorbed_attention,
)
from deeplearning_mpi_tpu.ops.pallas import latent_decode, latent_prefill
from deeplearning_mpi_tpu.serving.engine import EngineConfig, PagedForward, ServingEngine, _table_shapes
from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers
from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

KIMI = json.loads((ROOT / "benchmark/configs/kimi-k2.7-code-l5.json").read_text())
BS, CHUNK = 4, 8
CFG = {
    **KIMI, "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "moe_intermediate_size": 16, "n_routed_experts": 4, "router_experts": 16, "experts_first": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "vocab_size": 64, "torch_dtype": "float32",
    "rope_scaling": {**KIMI["rope_scaling"], "factor": 4, "original_max_position_embeddings": 32},
}
#: 24 blocks of 4 = 96 positions a sequence, three times YaRN's original length
ENGINE = EngineConfig(max_slots=4, block_size=BS, num_blocks=96, max_blocks_per_seq=24, prefill_chunk=CHUNK, max_queue=16)
MODEL = program.model_config(CFG)
RNG = np.random.default_rng(41)


@pytest.fixture(scope="module")
def params():
    return weights.build(CFG, weights.seed_words(41), jnp.float32)


def _prompt(n: int) -> np.ndarray:
    return RNG.integers(0, CFG["vocab_size"], n).astype(np.int32)


def _engine(params, engine=ENGINE, model=MODEL, **kw):
    return ServingEngine(model, params, engine, dtype=jnp.float32, **kw)


def _serve(engine, prompts, new):
    reqs = [engine.submit(p, new) for p in prompts]
    engine.run_until_idle()
    return [list(r.generated) for r in reqs]


def _reference_logits(params, prompt, served, **how):
    """The reference's logits at the position that served each token."""
    ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    return np.asarray(reference.serve_logits(CFG, params, ids, np.arange(len(prompt) - 1, len(ids)), **how))


# -- the model ---------------------------------------------------------------

def test_the_configuration_describes_latent_attention_and_the_share():
    spec = MODEL.latent
    assert (spec.q_rank, spec.kv_rank, spec.nope, spec.rope, spec.v) == (16, 16, 8, 8, 12) and MODEL.head_dim == 16
    assert MODEL.rope_dim == 8 and spec.scale == pytest.approx(program.softmax_scale(CFG))
    assert [MODEL.moe_layer(i) for i in range(3)] == [False, True, True] and MODEL.moe_layers == 2
    assert (MODEL.moe_router_width, MODEL.moe_first_expert, MODEL.moe_experts, MODEL.moe_shared_experts) == (16, 4, 4, 1)
    assert MODEL.expert_share and not dataclasses.replace(MODEL, moe_router_experts=0, moe_first_expert=0).expert_share
    assert MODEL.moe_scoring == "sigmoid" and MODEL.moe_routed_scale == 2.827 and MODEL.rms_norm_eps == 1e-5
    one_kind = TransformerConfig.tiny()
    assert one_kind.latent is None and one_kind.rope_dim == one_kind.head_dim and one_kind.rms_norm_eps == 1e-6
    with pytest.raises(NotImplementedError, match="without a query bottleneck"):
        dataclasses.replace(MODEL, q_lora_rank=0)
    with pytest.raises(ValueError, match="head_dim 12 of latent attention"):
        dataclasses.replace(MODEL, head_dim=12)
    with pytest.raises(NotImplementedError, match="sliding window or a learned selection"):
        dataclasses.replace(MODEL, layers=(LayerSpec(16),) + MODEL.layers[1:])
    with pytest.raises(NotImplementedError, match="are dropless_moe's"):
        dataclasses.replace(MODEL, moe_routing="token_choice")
    with pytest.raises(ValueError, match="held of a router of 16"):
        dataclasses.replace(MODEL, moe_first_expert=13)


def test_yarn_and_the_softmax_scale_at_the_published_numbers():
    """64 rope dims, theta 50,000, original 4,096, factor 64, beta 32 and 1:
    corr(32) = 8.92 and corr(1) = 19.16, so dimensions up to 8 keep their
    frequency, those from 20 on turn 64 times slower, a ramp between; cos and
    sin keep their scale (mscale = mscale_all_dim = 1), and the softmax scale
    carries mscale(64)^2 = (0.1 ln 64 + 1)^2."""
    published = program.model_config(KIMI)
    factor, original, fast, slow, attention_factor = published.layer_spec(0).yarn
    assert (factor, original, fast, slow, attention_factor) == (64.0, 4096, 32.0, 1.0, 1.0)
    inv = yarn_inv_freq(64, 50000.0, factor, original, fast, slow)
    f = lambda j: 50000.0 ** (-j / 32)  # noqa: E731
    corr = lambda turns: 64 * math.log(4096 / (2 * math.pi * turns)) / (2 * math.log(50000))  # noqa: E731
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (8, 20)
    np.testing.assert_allclose(inv[:9], [f(j) for j in range(9)], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], [f(j) / 64 for j in range(20, 32)], rtol=1e-6)
    np.testing.assert_allclose(inv[14], f(14) * (1 - 6 / 12) + f(14) / 64 * (6 / 12), rtol=1e-6)
    np.testing.assert_allclose(inv, reference.rope_freqs(KIMI)[0], rtol=1e-6)  # the reference's own
    assert published.softmax_scale == pytest.approx((0.1 * math.log(64) + 1) ** 2 / math.sqrt(192)) == pytest.approx(0.144680, abs=5e-7)
    assert published.softmax_scale == pytest.approx(reference.scale(KIMI, frozenset()))


def test_every_norm_reads_the_models_epsilon(params):
    """``rms_norm_eps`` reaches RMSNorm in the uncached forward and the
    engine's own norm: at an epsilon as large as the activations' mean
    square both move, and by the same amount."""
    x = jnp.asarray(RNG.normal(size=(2, 32)) * 0.01, jnp.float32)
    scale = jnp.ones((32,), jnp.float32)
    for eps in (1e-6, 1e-3):
        flax_norm = RMSNorm(eps).apply({"params": {"scale": scale}}, x)
        engine_norm = PagedForward(dataclasses.replace(MODEL, rms_norm_eps=eps), ENGINE, jnp.float32)._rmsnorm(x, scale)
        want = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        np.testing.assert_allclose(flax_norm, want, rtol=1e-6)
        np.testing.assert_allclose(engine_norm, want, rtol=1e-6)
    ids = jnp.asarray(_prompt(24))[None]
    big = dataclasses.replace(MODEL, rms_norm_eps=10.0)
    assert np.abs(np.asarray(TransformerLM(big, dtype=jnp.float32).apply({"params": params}, ids))
                  - np.asarray(TransformerLM(MODEL, dtype=jnp.float32).apply({"params": params}, ids))).max() > 0.01


def test_the_uncached_forward_is_the_reference(params):
    ids = _prompt(96)
    ours = TransformerLM(MODEL, dtype=jnp.float32).apply({"params": params}, jnp.asarray(ids)[None])[0]
    want = reference.serve_logits(CFG, params, ids, np.arange(96))
    # float32 on both sides: the difference is the order of the sums (the model's attention takes q_nope . k_nope and
    # q_pe . k_pe apart, the reference one 16-wide product), under 1e-5 at these widths
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), atol=1e-4)
    # and each of its parts matters there: a planted fault moves the logits
    for fault in reference.FAULTS:
        off = reference.serve_logits(CFG, params, ids, np.arange(96), faults=frozenset([fault]))
        assert np.abs(np.asarray(off) - np.asarray(want)).max() > 0.01, fault


def test_absorbed_attention_is_the_expanded_one():
    """One query a row over a latent of 40 positions, rows of unequal
    length and one inactive: the absorbed form (keys are the latent, the
    value projection after the sum) and the expanded one agree to float32
    rounding."""
    rng = np.random.default_rng(7)
    heads, nope, rope, v, kvr, length = 4, 8, 8, 12, 16, 40
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q_nope, q_pe, c, k_pe, w = arr(3, 1, heads, nope), arr(3, 1, heads, rope), arr(3, length, kvr), arr(3, length, rope), arr(kvr, heads, nope + v)
    index = jnp.asarray([39, 11, -1])
    valid = (jnp.arange(length)[None, :] <= index[:, None])[:, None]
    absorbed = absorbed_attention(q_nope, q_pe, c, k_pe, w, scale=0.3, valid=valid)
    expanded = expanded_attention(q_nope, q_pe, c, k_pe, w, scale=0.3, valid=valid)
    assert absorbed.shape == (3, 1, heads, v)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), rtol=1e-5, atol=1e-5)
    assert not np.asarray(absorbed[2]).any()  # an inactive row attends nothing


@pytest.mark.parametrize("start, length", [(0, 16), (3, 24), (16, 40), (24, 40)])
@pytest.mark.parametrize("blocks", [(8, 8), (16, 512)], ids=["tiled", "default"])
def test_the_prefill_chunk_kernel_is_the_expanded_attention(start, length, blocks):
    """A chunk of 16 queries at table positions ``start ..`` over a table of
    ``length``: the Pallas kernel (interpreted here) in tiles of 8 (several
    query blocks; key blocks before, across and wholly after a query block's
    diagonal, the last skipped) and in one tile, against the expanded form
    with its mask written out, at float32."""
    rng = np.random.default_rng(start + length)
    heads, nope, rope, v, kvr, chunk = 4, 8, 8, 12, 16, 16
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q_nope, q_pe, c, k_pe, w = arr(1, chunk, heads, nope), arr(1, chunk, heads, rope), arr(1, length, kvr), arr(1, length, rope), arr(kvr, heads, nope + v)
    valid = (jnp.arange(length)[None, :] <= start + jnp.arange(chunk)[:, None])[None]
    want = expanded_attention(q_nope, q_pe, c, k_pe, w, scale=0.3, valid=valid)
    kv = jnp.einsum("kc,chd->hkd", c[0], w)
    block_q, block_k = blocks
    got = latent_prefill.chunk_attention(
        q_nope[0], q_pe[0], kv[..., :nope], kv[..., nope:], k_pe[0], scale=0.3, start=jnp.int32(start),
        block_q=block_q, block_k=block_k, interpret=True,
    )
    assert got.shape == (chunk, heads, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]), rtol=1e-5, atol=1e-5)
    through_module = chunk_attention(q_nope, q_pe, c, k_pe, w, scale=0.3, start=jnp.int32(start))
    np.testing.assert_allclose(np.asarray(through_module), np.asarray(want), rtol=1e-5, atol=1e-5)


# -- the decode kernel --------------------------------------------------------

#: rows' lengths (0: an inactive row) and whether the pages lie in scrambled
#: pool order; tables of 24 blocks of 4
DECODE_CASES = {
    "length-1": ([1], False),
    "page-edge": ([2 * BS, 2 * BS + 1], False),
    "full-table": ([24 * BS], False),
    "inactive": ([7, 0], False),
    "scrambled": ([13, 22, 7], True),
    "16-rows": ([1, 4, 5, 9, 16, 17, 30, 0, 33, 47, 64, 65, 80, 95, 96, 2], True),
}


def _paged_case(lengths, scrambled, *, width=24, seed=0, blocks=400):
    """Pools of 2 layers, tables of ``width`` blocks holding each row's pages
    (``lengths``), ``last`` (-1 for an inactive row) and the operands."""
    rng = np.random.default_rng(seed)
    heads, nope, rope, v, kvr = 4, 8, 8, 12, 16
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    ids = rng.permutation(np.arange(1, blocks)) if scrambled else np.arange(1, blocks)
    tables, at = np.zeros((len(lengths), width), np.int32), 0
    for r, n in enumerate(lengths):
        pages = -(-n // BS)
        tables[r, :pages], at = ids[at : at + pages], at + pages
    return dict(
        q_nope=arr(len(lengths), 1, heads, nope), q_pe=arr(len(lengths), 1, heads, rope),
        c_pool=arr(2, blocks, BS, kvr), kpe_pool=arr(2, blocks, rope, BS),
        tables=jnp.asarray(tables), last=jnp.asarray(np.asarray(lengths) - 1, jnp.int32),
        w_kvb=arr(kvr, heads, nope + v),
    )


def _paged(case, layer=1):
    return paged_absorbed_attention(
        case["q_nope"], case["q_pe"], case["c_pool"], case["kpe_pool"], layer,
        case["tables"], case["last"], case["w_kvb"], scale=0.3,
    )


@pytest.mark.parametrize("group", [2 * BS, latent_decode.GROUP_POSITIONS], ids=["two-page-groups", "one-group"])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_the_decode_kernel_is_the_absorbed_attention(case, group, monkeypatch):
    """The Pallas decode kernel (interpreted here, float32) walks each row's
    live pages through its block table, in groups of two pages (a walk of
    several groups, the last one half read) and in one group, against
    :func:`absorbed_attention` over the table's gathered pages: a row of one
    position, rows ending on a page's edge and one past it, a row that fills
    its table, an inactive row (zeros), pages in scrambled pool order, 1 and
    16 rows."""
    monkeypatch.setattr(latent_decode, "latent_decode", functools.partial(latent_decode.latent_decode, group_positions=group))
    lengths, scrambled = DECODE_CASES[case]
    op = _paged_case(lengths, scrambled, seed=len(lengths) + sum(lengths))
    rows, width = op["tables"].shape
    c = op["c_pool"][1][op["tables"]].reshape(rows, width * BS, -1)
    k_pe = jnp.swapaxes(op["kpe_pool"][1][op["tables"]], -1, -2).reshape(rows, width * BS, -1)
    valid = (jnp.arange(width * BS)[None, :] <= op["last"][:, None])[:, None]
    want = absorbed_attention(op["q_nope"], op["q_pe"], c, k_pe, op["w_kvb"], scale=0.3, valid=valid)
    got = _paged(op)
    assert got.shape == want.shape == (rows, 1, 4, 12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[np.asarray(lengths) == 0].any()


def test_a_rows_decode_output_is_the_same_at_two_widths_and_beside_other_rows():
    """Row independence with one decode width: a row's output, bit for bit,
    first in a table of 8 blocks and last in one of 24, beside other rows
    (the walk's groups are whole pages from the row's first whatever the
    table's width). The batches have as many rows: XLA:CPU's products of
    the absorption round a row by the batch's size."""
    row = _paged_case([30, 0, 0], True, width=8, seed=3)
    narrow = _paged_case([30, 5, 17], True, width=8, seed=5)
    wide = _paged_case([11, 60, 30], True, width=24, seed=4)
    for op, at in ((narrow, 0), (wide, 2)):  # the row's query, latent and pages in each batch
        for name in ("q_nope", "q_pe"):
            op[name] = op[name].at[at].set(row[name][0])
        op["c_pool"], op["kpe_pool"] = row["c_pool"], row["kpe_pool"]
        op["tables"] = op["tables"].at[at].set(0).at[at, :8].set(row["tables"][0])
        op["w_kvb"] = row["w_kvb"]
    alone = np.asarray(_paged(row))[0]
    np.testing.assert_array_equal(np.asarray(_paged(narrow))[0], alone)
    np.testing.assert_array_equal(np.asarray(_paged(wide))[2], alone)


# -- the expert layer --------------------------------------------------------

def _layer_weights(seed: int, experts: int, d: int = 16, f: int = 8):
    rng = np.random.default_rng(seed)
    arr = lambda *s, scale=1.0: jnp.asarray(rng.normal(size=s) * scale, jnp.float32)  # noqa: E731
    shared = {n: {"kernel": arr(*s, scale=s[0] ** -0.5)} for n, s in (("gate_proj", (d, f)), ("up_proj", (d, f)), ("down_proj", (f, d)))}
    return (
        arr(d, experts, scale=d**-0.5), arr(experts, scale=0.1),
        arr(experts, d, f, scale=d**-0.5), arr(experts, d, f, scale=d**-0.5), arr(experts, f, d, scale=f**-0.5), shared,
    )


@pytest.mark.parametrize("rows", [2, 32], ids=["grouped", "batched"])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """16 experts at top-4 over 4 chips of 4: the parts the four shares give,
    with the shared expert counted once, are what the whole layer gives."""
    router, bias, gate, up, down, shared = _layer_weights(3, 16)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(rows, 16)), jnp.float32)
    kw = dict(top_k=4, dtype=jnp.float32, bias=bias)
    whole, touched = dropless_moe(x, router, gate, up, down, routing=Routing("sigmoid", 2.827), shared=shared, **kw)
    parts, counts = [], []
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        part, count = dropless_moe(
            x, router, gate[held], up[held], down[held], routing=Routing("sigmoid", 2.827, 16, first), count_claims=True, **kw,
        )
        assert dropless_form(rows, 4, 16) == ("grouped" if rows == 2 else "batched")
        parts.append(part)
        counts.append(np.asarray(count))
    shared_out = dropless_moe(x, router, gate[:0], up[:0], down[:0], routing=Routing("sigmoid", 2.827, 16, 0), shared=shared, **kw)[0]
    np.testing.assert_allclose(np.asarray(sum(parts) + shared_out), np.asarray(whole), rtol=1e-5, atol=1e-5)
    # every claim lands on exactly one share; touched experts add up
    assert sum(c[1] for c in counts) == rows * 4 and sum(c[0] for c in counts) == int(touched)


def test_the_bias_picks_and_does_not_weigh():
    """With a bias that overrules the scores, the chosen experts are the
    top-k of scores + bias, weighted by 2.827 times their scores
    renormalised (a hand computation), not by scores + bias."""
    router, _, gate, up, down, _ = _layer_weights(4, 8)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 16)), jnp.float32)
    bias = jnp.asarray([5.0, 0, 0, 5.0, 0, 0, 0, 0], jnp.float32)  # experts 0 and 3 always chosen at top-2
    y, _ = dropless_moe(x, router, gate, up, down, top_k=2, dtype=jnp.float32, routing=Routing("sigmoid", 2.827), bias=bias)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    expert = lambda e: np.asarray(jax.nn.silu(x @ gate[e]) * (x @ up[e]) @ down[e])  # noqa: E731
    weigh = lambda s: s / s.sum(axis=-1, keepdims=True) * 2.827  # noqa: E731
    g = weigh(scores[:, [0, 3]])
    want = g[:, :1] * expert(0) + g[:, 1:] * expert(3)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-6)
    g_wrong = weigh(scores[:, [0, 3]] + 5.0)
    assert np.abs(g_wrong[:, :1] * expert(0) + g_wrong[:, 1:] * expert(3) - want).max() > 1e-3
    assert np.argsort(scores, axis=-1)[:, -2:].tolist() != [[0, 3]] * 3  # the scores alone would pick others


def test_the_form_is_reckoned_against_the_router():
    """A share's claims spread over the router's experts: 16 rows at top-8
    over 384 are grouped whether 12 or all 384 are held; held = router is
    the rule as it was."""
    assert dropless_form(16, 8, 384) == "grouped" and dropless_form(48, 8, 384) == "batched"
    assert dropless_form(16, 8, 64) == "batched" and dropless_form(8, 8, 128) == "grouped"
    # the engine's launch label asks the same rule of the same width
    assert ServingEngine._moe_form(types.SimpleNamespace(config=MODEL), 4) == {"moe": dropless_form(4, 4, 16)}


# -- the engine against the reference ----------------------------------------

def test_served_tokens_and_last_chunk_logits_are_the_references(params):
    """Chunked prefill over several chunks and blocks (the expanded form),
    then 20 decode steps through the latent pool (the absorbed form) past
    YaRN's original length, short and long rows in one batch. Both sides
    float32: the served token is the reference's best (gap < 1e-4: a
    rounding of the two forms' sums can only tie it) and the last chunk's
    logits are the reference's at that position to 2e-4."""
    engine = _engine(params)
    last_logits = {}
    prefill = engine._prefill_fn

    def spy(*args):
        out = prefill(*args)
        last_logits[int(args[4]) + int(args[5])] = np.asarray(out[1])  # by the position the chunk ends at
        return out

    engine._prefill_fn = spy
    prompts = [_prompt(5), _prompt(21), _prompt(43), _prompt(60)]
    served = _serve(engine, prompts, 20)
    for prompt, tokens in zip(prompts, served):
        logits = _reference_logits(params, prompt, tokens)
        assert len(tokens) == 20 and check.served_gap(logits, tokens) < 1e-4
        np.testing.assert_allclose(last_logits[len(prompt)], logits[0], atol=2e-4)
    engine.pool.check()
    assert engine.pool.in_use == 0


def test_the_latent_pool_holds_one_vector_a_position(params):
    """``[c ; k_pe]`` a position a layer, in the two arrays where K and V
    would be: 16 + 8 values, and no V pool; a block of ``k_pe`` holds its
    positions minor."""
    engine = _engine(params)
    c, k_pe = engine._kv
    assert c.shape == (3, ENGINE.num_blocks, BS, 16) and k_pe.shape == (3, ENGINE.num_blocks, 8, BS)
    assert engine._kvh.nbytes == 3 * ENGINE.num_blocks * BS * (16 + 8) * 4
    assert init_kv_buffers(3, 8, BS, 4, 16, jnp.float32, latent_dims=(16, 8))[0].shape == (3, 8, BS, 16)
    with pytest.raises(NotImplementedError, match="integer storage"):
        init_kv_buffers(3, 8, BS, 4, 16, jnp.int8, latent_dims=(16, 8))


def test_a_request_alone_and_among_strangers(params):
    prompt = _prompt(37)
    alone = _serve(_engine(params), [prompt], 24)[0]
    crowd = _serve(_engine(params), [_prompt(11), prompt, _prompt(52), _prompt(29)], 24)[1]
    assert alone == crowd


def test_the_prefix_cache_adopts_latent_blocks(params):
    """A follow-up turn on a 22-position prompt adopts its latent blocks
    (five whole ones, and a copy of the partial sixth to write its own
    positions into) and serves what a cold engine serves."""
    first = _prompt(22)
    follow = np.concatenate([first, _prompt(9)])
    registry = MetricsRegistry()
    cached = _engine(params, engine=dataclasses.replace(ENGINE, prefix_cache=True), registry=registry)
    _serve(cached, [first], 4)
    warm = _serve(cached, [follow], 12)[0]
    snap = registry.snapshot()
    assert snap["serve_prefix_hits_total"] == 1 and snap["serve_prefix_tokens_reused_total"] == 22
    assert snap["serve_prefix_cow_copies_total"] == 1  # positions 20, 21 of block 5, copied for the new tail
    assert warm == _serve(_engine(params), [follow], 12)[0]


def test_zero_compiles_after_warmup(params):
    registry = MetricsRegistry()
    engine = _engine(params, registry=registry)
    programs = engine.warmup()
    assert len(programs) == len(engine._decode_shapes) + len(engine._widths)
    compiled = registry.snapshot()["serve_compile_total"]
    served = _serve(engine, [_prompt(3), _prompt(14), _prompt(33), _prompt(50)], 30)
    assert all(len(t) == 30 for t in served)
    assert registry.snapshot()["serve_compile_total"] == compiled
    assert engine._decode_fn.fallback_calls == 0 and engine._prefill_fn.fallback_calls == 0


def test_a_latent_engine_warms_one_decode_width_at_each_row_bucket(params):
    """A latent model's decode programs take the full table at every row
    bucket (the kernel walks live pages whatever the width): 3 decode
    programs (here every bucket takes the expert layer's grouped form: top-1
    of 16) and the 4 prefill widths; the widest decode program's kernel
    calls are the gauge ``serve_decode_kernel_calls`` (Mosaic calls: the
    interpreter here makes none; a described-v5e compile of the program
    counts one a layer, ``tests/test_loss.py``). A dense model's ladders are
    ``_table_shapes``' as ever, and its gauge reads 0."""
    registry = MetricsRegistry()
    engine = _engine(params, model=dataclasses.replace(MODEL, moe_top_k=1), registry=registry)
    programs = engine.warmup()
    assert engine._decode_shapes == ((1, 24), (2, 24), (4, 24)) and len(engine._widths) == 4
    assert sorted(programs) == sorted(
        ["serve_decode_step@1x24", "serve_decode_step@2x24", "serve_decode_step"]
        + ["serve_prefill_chunk@4", "serve_prefill_chunk@8", "serve_prefill_chunk@16", "serve_prefill_chunk"]
    )
    widest = programs["serve_decode_step"].compiled
    assert registry.snapshot()["serve_decode_kernel_calls"] == aot.mosaic_call_count(widest, kernel=latent_decode.NAME)
    dense_cfg = TransformerConfig.tiny()
    dense_params = TransformerLM(dense_cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    dense_registry, small = MetricsRegistry(), dataclasses.replace(ENGINE, max_slots=1, max_blocks_per_seq=2)
    dense = ServingEngine(dense_cfg, dense_params, small, dtype=jnp.float32, registry=dense_registry)
    assert (dense._widths, dense._decode_shapes) == _table_shapes(1, 2) == ((1, 2), ((1, 1), (1, 2)))
    dense.warmup()
    assert dense_registry.snapshot()["serve_decode_kernel_calls"] == 0


def test_the_launch_labels_and_counters(params, monkeypatch):
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
    reqs = [engine.submit(p, 16) for p in (_prompt(30), _prompt(7))]
    engine.run_until_idle()
    decode = [s.labels for s in spans if s.name == "serve/decode_launch"]
    prefill = [s.labels for s in spans if s.name == "serve/prefill_launch"]
    assert decode and all(d["attn"] == "latent_absorbed" and d["gathered"] == d["table_rows"] * d["width"] * BS for d in decode)
    assert all(d["live"] <= d["gathered"] for d in decode) and {d["moe"] for d in decode} == {dropless_form(4, 4, 16)}
    assert prefill and all(p["attn"] == "latent_expanded" and p["live"] == p["start"] + p["n"] for p in prefill)
    snap = registry.snapshot()
    steps = snap["serve_decode_steps"]
    assert steps == len(decode) and snap["serve_moe_expert_slots"] == steps * 2 * 4  # two expert layers of 4 held
    assert snap["serve_moe_claims"] == sum(d["rows"] for d in decode) * 4 * 2
    assert 0 < snap["serve_moe_claims_held"] < snap["serve_moe_claims"]  # about a quarter: 4 of 16 experts held
    assert 0 < snap["serve_moe_experts_touched"] <= snap["serve_moe_expert_slots"]
    assert snap["serve_kv_bytes"] == engine._kvh.nbytes
    assert all(len(r.generated) == 16 for r in reqs)


@pytest.mark.parametrize("change, kw, match", [
    ({"spec_k": 2}, "draft", "spec_k > 0"),
    ({"kv_dtype": "int8"}, None, "kv_dtype='int8'"),
    ({}, "pool", "a disaggregated hand-off"),
    ({}, "role", "a disaggregated hand-off"),
], ids=["speculation", "int8", "injected-pool", "role"])
def test_what_the_latent_pool_does_not_serve_is_refused_by_name(params, change, kw, match):
    from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

    extra = {
        None: {}, "draft": {"draft_config": TransformerConfig.tiny(), "draft_params": params},
        "pool": {"pool": PagedKVPool(ENGINE.num_blocks, BS)}, "role": {"role": "prefill"},
    }[kw]
    with pytest.raises(NotImplementedError, match=f"a model with latent attention is not served with {match}"):
        _engine(params, engine=dataclasses.replace(ENGINE, **change), **extra)
