"""The token cross-entropy against its plain spelling, and what it may not build.

``ops/loss.py`` computes ``logsumexp(x) - x[label]`` with the label picked by
an iota comparison, over all S rows of the LM's logits with the labels
shifted. The spelling it replaced, ``-take_along_axis(log_softmax(f32(logits
[:, :-1])), labels)``, stays here as the reference: same values and
gradients, but it wrote a float32 log-prob tensor forward, scattered into a
zero-filled logits-sized buffer backward and copied an S-1-row slice.

The file also holds the suite's compiles for a described v5e (the head and
loss at ``lm-train-8k``'s shape; the train step's gradient reductions on a
2x2 data mesh), which must stay in one file.
"""

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_mpi_tpu.ops import (
    chunked_lm_loss,
    lm_cross_entropy,
    masked_mean,
    softmax_cross_entropy,
)

# Neither the sequence nor the vocabulary tiles by 8 x 128.
B, S, D, V = 2, 13, 8, 37
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def ref_nll(logits, labels):
    log_probs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def ref_lm(logits, tokens, mask=None):
    nll = ref_nll(logits[:, :-1], tokens[:, 1:])
    return masked_mean(nll, None if mask is None else mask[:, 1:])


def ref_sce(logits, labels, where=None):
    return masked_mean(ref_nll(logits, labels), where)


def _data(dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, S, D)), dtype)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.5, dtype)
    tokens = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
    return x, w, tokens, mask


def _lm_case(masked):
    def build(dtype):
        x, w, tokens, mask = _data(dtype)
        m = mask if masked else None
        return (
            lambda logits: lm_cross_entropy(logits, tokens, m),
            lambda logits: ref_lm(logits, tokens, m),
            (x @ w,),
        )
    return build


def _sce_case(masked):
    def build(dtype):
        x, w, tokens, mask = _data(dtype, seed=1)
        labels, where = tokens[:, 0], (mask[:, 0].at[0].set(1.0) if masked else None)
        return (
            lambda logits: softmax_cross_entropy(logits, labels, where),
            lambda logits: ref_sce(logits, labels, where),
            ((x @ w)[:, 0],),
        )
    return build


def _chunked_case(masked, chunk):
    def build(dtype):
        x, w, tokens, mask = _data(dtype, seed=2)
        m = mask if masked else None
        return (
            lambda x, w: chunked_lm_loss(x, w, tokens, chunk_size=chunk, mask=m),
            lambda x, w: ref_lm(x @ w, tokens, m),
            (x, w),
        )
    return build


CASES = {
    "lm": _lm_case(False),
    "lm_masked": _lm_case(True),
    "classifier": _sce_case(False),
    "classifier_where": _sce_case(True),
    "chunked": _chunked_case(False, 5),
    "chunked_masked": _chunked_case(True, 4),
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_value_and_gradient_match_the_plain_spelling(case, dtype):
    ours, ref, args = CASES[case](DTYPES[dtype])
    argnums = tuple(range(len(args)))
    value, grads = jax.value_and_grad(ours, argnums)(*args)
    ref_value, ref_grads = jax.value_and_grad(ref, argnums)(*args)
    assert value.dtype == jnp.float32
    np.testing.assert_allclose(float(value), float(ref_value), rtol=2e-6)
    # Tolerances from the dtype: float32 rounding of a different order of the
    # same sums; in bfloat16 the gradient is rounded once where it is
    # produced (one ulp = 2**-8), and the chunked head adds its chunks'
    # weight gradients in bfloat16 (a few ulp more).
    eps = {"float32": 2e-6, "bfloat16": 2.0**-7 * (4 if "chunked" in case else 1)}[dtype]
    for got, want, arg in zip(grads, ref_grads, args):
        assert got.dtype == arg.dtype and got.shape == arg.shape
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=eps, atol=eps * np.abs(want).max())


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_last_position_gets_an_exactly_zero_gradient(masked, dtype):
    ours, _, (logits,) = _lm_case(masked)(DTYPES[dtype])
    grad = np.asarray(jax.grad(ours)(logits), np.float32)
    assert not grad[:, -1].any()
    assert grad[:, :-1].any()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf], ids=["nan", "inf", "neg_inf"])
def test_non_finite_logit_at_the_last_position_changes_nothing(poison, dtype):
    x, w, tokens, mask = _data(DTYPES[dtype], seed=3)
    logits = x @ w
    poisoned = logits.at[:, -1, 3].set(poison).at[0, -1].set(poison)
    for m in (None, mask):
        loss = lambda l: lm_cross_entropy(l, tokens, m)  # noqa: E731
        value, grad = jax.value_and_grad(loss)(logits)
        p_value, p_grad = jax.value_and_grad(loss)(poisoned)
        assert np.isfinite(float(value)) and float(p_value) == float(value)
        np.testing.assert_array_equal(np.asarray(p_grad, np.float32), np.asarray(grad, np.float32))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_all_zero_mask_gives_zero_loss_and_zero_gradients(loss, dtype):
    x, w, tokens, mask = _data(DTYPES[dtype], seed=4)
    zeros = jnp.zeros_like(mask)
    fn = {
        "dense": lambda x, w: lm_cross_entropy(x @ w, tokens, zeros),
        "chunked": lambda x, w: chunked_lm_loss(x, w, tokens, chunk_size=5, mask=zeros),
    }[loss]
    value, grads = jax.value_and_grad(fn, (0, 1))(x, w)
    assert float(value) == 0.0
    for g in grads:
        assert not np.asarray(g, np.float32).any()


# ---- what the loss may not build -------------------------------------------


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _primitives(fn, *args):
    return {eqn.primitive.name for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)}


def _logits_sized_f32_residuals(fn, logits):
    """Float32 arrays over the vocabulary that the forward keeps for the backward."""
    _, pullback = jax.vjp(fn, logits)
    return [
        leaf.shape
        for leaf in jax.tree.leaves(pullback)
        if leaf.dtype == jnp.float32 and leaf.shape[-1:] == logits.shape[-1:] and leaf.ndim == logits.ndim
    ]


def test_gradient_has_no_scatter_or_gather_and_saves_no_f32_logits():
    x, w, tokens, mask = _data(jnp.bfloat16)
    logits = x @ w
    for m in (None, mask):
        ours = lambda l: lm_cross_entropy(l, tokens, m)  # noqa: E731
        ref = lambda l: ref_lm(l, tokens, m)  # noqa: E731
        prims = _primitives(jax.grad(ours), logits)
        assert not {p for p in prims if p.startswith(("scatter", "gather"))}, prims
        assert _logits_sized_f32_residuals(ours, logits) == []
        # The walker and the residual reader do see what the plain spelling builds.
        assert "scatter-add" in _primitives(jax.grad(ref), logits)
        assert _logits_sized_f32_residuals(ref, logits)


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described (not attached) v5e host of four chips to compile for.
    Describing it loads libtpu into the test process, so it is built once per
    module, and every described compile of the suite lives in this one file."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return jax.sharding.SingleDeviceSharding(v5e_2x2.devices[0])


@contextlib.contextmanager
def _no_compile_cache():
    """A compile for a described device can be written to the persistent
    cache but not read back; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_head_and_loss_compile_for_v5e_without_a_logits_sized_detour(one_chip):
    """The head matmul, the loss and their gradients at ``lm-train-8k``'s
    shape: the chip's compiler keeps the logits and their gradient once
    each, in bfloat16, and moves 4.4 GB (3.35 GB are the three matmuls'; the
    plain spelling moved 11.6 GB through an f32 log-prob tensor, a scatter
    buffer and a copied 8,191-row slice, with 2.1 GB of temporaries)."""
    seq, d_model, vocab = 8192, 4096, 32000

    def step(x, w, tokens):
        loss = lambda x, w: lm_cross_entropy(jnp.einsum("bsd,dv->bsv", x, w), tokens)  # noqa: E731
        return jax.value_and_grad(loss, (0, 1))(x, w)

    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    with _no_compile_cache():
        compiled = jax.jit(step).lower(
            aval((1, seq, d_model), jnp.bfloat16), aval((d_model, vocab), jnp.bfloat16), aval((1, seq), jnp.int32)
        ).compile()
    # Instructions of the entry computation are what reaches memory; inside
    # a fusion a float32 value of the logits' shape lives in registers.
    text = compiled.as_text()
    text = text[text.index("\nENTRY "):]
    for shape in ("f32[8191,32000]", "f32[8192,32000]", "f32[1,8191,32000]", "f32[1,8192,32000]", "[262112000]", "[262144000]", "bf16[1,8191,32000]"):
        assert shape not in text, shape
    assert compiled.cost_analysis()["bytes accessed"] < 4.6e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1e9


# A small LM whose step compiles for the chip in seconds: 2 layers, published
# Mistral head width, the sharded flash kernel at a sequence it tiles.
_LM = dict(vocab_size=2048, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=128,
           d_model=512, d_ff=2048, attention_window=512)
_SEQ = 1024


def _described_step_args(mesh, monkeypatch):
    """The abstract train state and batch of the small LM, placed on ``mesh``
    (described devices) as ``Trainer.place_state`` would: replicated state,
    one row a device on ``data``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.parallel import infer_state_sharding, make_flash_attention_fn
    from deeplearning_mpi_tpu.train import TrainState
    from deeplearning_mpi_tpu.train.trainer import build_optimizer

    # The flash kernel picks Mosaic by the default backend, which is the CPU here.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(config=TransformerConfig(**_LM), dtype=jnp.bfloat16,
                          attention_fn=make_flash_attention_fn(mesh))
    tx = build_optimizer("adam", 3e-4, clip_norm=1.0)

    def make():
        params = model.init(jax.random.key(0), jnp.zeros((1, _SEQ), jnp.int32))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
                          opt_state=tx.init(params), apply_fn=model.apply, tx=tx)

    abstract = jax.eval_shape(make)
    shardings = infer_state_sharding(abstract, mesh, zero=False)
    state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), abstract, shardings)
    rows = mesh.devices.size
    batch = {"tokens": jax.ShapeDtypeStruct((rows, _SEQ), jnp.int32, sharding=NamedSharding(mesh, P("data")))}
    return state, batch


def _entry(text):
    start = text.index("\nENTRY ")
    return text[start:text.index("\n}\n", start)]


def test_dp_train_step_reduces_every_gradient_inside_an_async_fusion_on_v5e(v5e_2x2, monkeypatch):
    """On four described chips with data 4, ``Trainer.warmup`` compiles the
    step with the TPU options: every weight gradient's all-reduce is a step
    of an async collective fusion (inside a fused computation), none is a
    bare ``all-reduce`` in the entry computation, and the gauges read what
    the HLO holds."""
    from deeplearning_mpi_tpu.compiler import aot
    from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh
    from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry
    from deeplearning_mpi_tpu.train import Trainer

    mesh = create_mesh(MeshSpec(data=4), devices=list(v5e_2x2.devices))
    state, batch = _described_step_args(mesh, monkeypatch)
    trainer = Trainer(state, "lm", mesh, metrics=MetricsRegistry(), logger=None)
    with _no_compile_cache():
        prog = trainer.warmup(batch)
    text = prog.compiled.as_text()
    entry = _entry(text)
    assert "tpu_custom_call" in text  # the flash kernel, as on the chip
    for layer in range(_LM["num_layers"]):
        for proj in ("gate_proj", "up_proj", "down_proj"):
            op = f"layer_{layer}/mlp/{proj}/dot_general"
            reductions = [l for l in text.splitlines() if " all-reduce(" in l and op in l]
            assert reductions, op
            assert not [l for l in entry.splitlines() if " all-reduce(" in l and op in l], op
    n_async, n_sync = aot.collective_counts(prog.compiled)
    starts = [l for l in entry.splitlines() if l.lstrip().startswith(("%async-collective-start", "ROOT %async-collective-start"))]
    bare = [l for l in entry.splitlines() if " all-reduce(" in l or " all-gather(" in l or " reduce-scatter(" in l]
    assert n_async == len(starts) >= 2 * 3 * _LM["num_layers"]
    assert n_sync == len(bare)
    assert trainer.metrics.gauge("train_step_async_collectives").value == n_async
    assert trainer.metrics.gauge("train_step_sync_collectives").value == n_sync


def test_one_chip_train_step_hlo_is_unchanged_by_the_tpu_options(v5e_2x2, monkeypatch):
    """On one described chip the step holds no collective, and the options
    change nothing: the optimized HLO with them equals the HLO without,
    metadata stripped."""
    import re

    from deeplearning_mpi_tpu.runtime.mesh import MeshSpec, create_mesh
    from deeplearning_mpi_tpu.train.trainer import make_train_step, step_compiler_options

    mesh = create_mesh(MeshSpec(data=1), devices=[v5e_2x2.devices[0]])
    assert step_compiler_options(mesh.devices.flat)
    state, batch = _described_step_args(mesh, monkeypatch)
    strip = lambda t: re.sub(r", metadata=\{[^}]*\}", "", t)  # noqa: E731
    with _no_compile_cache():
        texts = [
            strip(make_train_step("lm", donate=False, **kw).lower(state, batch).compile().as_text())
            for kw in ({"mesh": mesh}, {})
        ]
    assert "tpu_custom_call" in texts[0] and " all-reduce(" not in texts[0]
    assert texts[0] == texts[1]


#: the latent decode program's described-v5e compile: rows, table width,
#: block size and latent width of ``kimi-serve-long``, and 2 of its layers
KIMI_ROWS, KIMI_WIDTH, KIMI_BS, KIMI_LAYERS = 16, 576, 128, 2

#: a child process lowers that program for the TPU (lowering needs no chip)
#: and prints its text's digest, the kernel's lowered bodies and call sites
_LOWER_LATENT_DECODE = """
import hashlib, json
import jax, jax.numpy as jnp
from benchmark.kimi import program, weights
from benchmark.manifest import ROOT
from deeplearning_mpi_tpu.ops.pallas import latent_decode
from deeplearning_mpi_tpu.serving.engine import EngineConfig, PagedForward
from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers
latent_decode._on_tpu = lambda: True
cfg = {**json.loads((ROOT / "benchmark/configs/kimi-k2.7-code-l5.json").read_text()), "num_hidden_layers": 2}
engine = EngineConfig(**{k: v for k, v in cfg["engine"].items() if k != "why"})
params = jax.eval_shape(lambda: weights.build(cfg, weights.seed_words(1), jnp.bfloat16))
pools = jax.eval_shape(lambda: init_kv_buffers(2, 4801, 128, 64, 192, jnp.bfloat16, latent_dims=(512, 64)))
i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
fwd = PagedForward(program.model_config(cfg), engine, jnp.bfloat16, window_cut=True)
text = jax.jit(fwd.decode_step, donate_argnums=(1,)).trace(
    params, pools, i32(16, 576), i32(16), i32(16), jax.ShapeDtypeStruct((16,), jnp.bool_),
).lower(lowering_platforms=("tpu",)).as_text()
print(hashlib.sha256(text.encode()).hexdigest(), text.count("tpu_custom_call"), text.count("call @latent_decode"))
"""


def _latent_decode_args(sharding):
    """The decode step of ``kimi-serve-long``'s engine (:data:`KIMI_LAYERS`
    of its layers) and its arguments as shapes on ``sharding``."""
    import json

    from benchmark.kimi import program, weights
    from benchmark.manifest import ROOT
    from deeplearning_mpi_tpu.serving.engine import EngineConfig, PagedForward
    from deeplearning_mpi_tpu.serving.kv_pool import init_kv_buffers

    cfg = {**json.loads((ROOT / "benchmark/configs/kimi-k2.7-code-l5.json").read_text()), "num_hidden_layers": KIMI_LAYERS}
    engine = EngineConfig(**{k: v for k, v in cfg["engine"].items() if k != "why"})
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)  # noqa: E731
    params = placed(jax.eval_shape(lambda: weights.build(cfg, weights.seed_words(1), jnp.bfloat16)))
    pools = placed(jax.eval_shape(
        lambda: init_kv_buffers(KIMI_LAYERS, engine.num_blocks, KIMI_BS, 64, 192, jnp.bfloat16, latent_dims=(512, 64))
    ))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)  # noqa: E731
    fwd = PagedForward(program.model_config(cfg), engine, jnp.bfloat16, window_cut=True)
    return fwd.decode_step, (
        params, pools, i32(KIMI_ROWS, KIMI_WIDTH), i32(KIMI_ROWS), i32(KIMI_ROWS),
        jax.ShapeDtypeStruct((KIMI_ROWS,), jnp.bool_, sharding=sharding),
    )


@pytest.fixture(scope="module")
def latent_decode_v5e(one_chip):
    """The serving engine's decode step of a latent-attention model at
    ``kimi-serve-long``'s widths, pool and table, compiled for a described
    v5e with its attention kernel in Mosaic (this process has no TPU, so
    the program would otherwise interpret it)."""
    from deeplearning_mpi_tpu.ops.pallas import latent_decode

    step, args = _latent_decode_args(one_chip)
    with pytest.MonkeyPatch.context() as mp, _no_compile_cache():
        mp.setattr(latent_decode, "_on_tpu", lambda: True)
        return jax.jit(step, donate_argnums=(1,)).lower(*args).compile()


def test_the_latent_decode_step_compiles_for_v5e_without_copying_its_pool(latent_decode_v5e):
    """The latent pool's two arrays, ``c`` of 512 and ``k_pe`` of 64 (its
    blocks with their positions minor), are scattered into and read from in
    place. Kept as ONE array of 576 a position, not a multiple of the chip's
    128-wide tiles, the compiler held the pool in another layout and copied
    the whole of it back and forth around every layer's scatter and
    gather."""
    text = latent_decode_v5e.as_text()
    text = text[text.index("\nENTRY "):]
    copies = [line for line in text.splitlines() if " copy(" in line]
    assert not [c for c in copies if "bf16[2,4801," in c]  # neither of the pool's arrays


def test_the_latent_decode_step_calls_its_kernel_at_every_layer_on_v5e(latent_decode_v5e):
    """Mosaic takes the decode kernel (``ops/pallas/latent_decode.py``) at
    each latent layer: what the engine's gauge ``serve_decode_kernel_calls``
    reads off its widest decode program."""
    from deeplearning_mpi_tpu.compiler import aot
    from deeplearning_mpi_tpu.ops.pallas import latent_decode

    assert "tpu_custom_call" in latent_decode_v5e.as_text()
    assert aot.mosaic_call_count(latent_decode_v5e, kernel=latent_decode.NAME) == KIMI_LAYERS


def test_the_latent_decode_steps_temporaries_hold_no_page_rectangle_on_v5e(latent_decode_v5e):
    """The kernel reads the pages in place: the program's temporaries stay
    under ONE ``[rows, width x BS, 512]`` rectangle of latent pages, which
    the absorbed form in XLA gathered into HBM (nine times a step)."""
    rectangle = KIMI_ROWS * KIMI_WIDTH * KIMI_BS * 512 * 2
    assert latent_decode_v5e.memory_analysis().temp_size_in_bytes < rectangle


def test_the_latent_decode_step_lowers_to_one_text_under_any_hash_seed():
    """The persistent compile cache's key is the lowered program: two
    processes with different ``PYTHONHASHSEED`` lower the decode step (at
    the cell's widths, for the TPU) to the same text, so a warm start finds
    what a cold one compiled. In it the Mosaic kernel is lowered once and
    called at each layer."""
    import subprocess
    import sys

    from benchmark.manifest import ROOT

    children = [
        subprocess.Popen(
            [sys.executable, "-c", _LOWER_LATENT_DECODE], cwd=ROOT, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)},
        )
        for seed in ("1", "2")
    ]
    outs = [child.communicate(timeout=600)[0].split() for child in children]
    assert [child.returncode for child in children] == [0, 0]
    assert outs[0] == outs[1] and outs[0][1:] == ["1", str(KIMI_LAYERS)]


def test_the_decode_kernel_is_traced_once_a_shape_in_a_latent_engines_warmup(monkeypatch):
    """A small latent engine's warm-up (3 latent layers, 3 decode programs:
    one a row bucket) traces the kernel's body once a program, not once a
    layer: every layer calls the one jitted kernel with the same shapes."""
    import json

    from benchmark.kimi import program, weights
    from benchmark.manifest import ROOT
    from deeplearning_mpi_tpu.ops.pallas import latent_decode
    from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine

    kimi = json.loads((ROOT / "benchmark/configs/kimi-k2.7-code-l5.json").read_text())
    cfg = {
        **kimi, "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
        "moe_intermediate_size": 16, "n_routed_experts": 4, "router_experts": 16, "experts_first": 4,
        "num_experts_per_tok": 1, "num_hidden_layers": 3, "vocab_size": 64, "torch_dtype": "float32",
    }
    traced = []
    kernel = latent_decode._kernel

    def counted(*refs, **kw):
        traced.append(kw)
        return kernel(*refs, **kw)

    monkeypatch.setattr(latent_decode, "_kernel", counted)
    # a pool of 44 blocks no other test builds: no trace of the kernel is cached
    engine = ServingEngine(
        program.model_config(cfg), weights.build(cfg, weights.seed_words(44), jnp.float32),
        EngineConfig(max_slots=4, block_size=4, num_blocks=44, max_blocks_per_seq=8, prefill_chunk=8),
        dtype=jnp.float32,
    )
    engine.warmup()
    assert len(engine._decode_shapes) == 3 and len(traced) == 3  # 9 were it traced a layer


def test_the_latent_prefill_kernel_compiles_for_v5e_at_the_cells_widths(one_chip):
    """The prefill chunk's attention of a latent-attention model at
    ``kimi-serve-long``'s widths: 1,024 queries of 64 heads (128 + 64 wide,
    values of 128) over the longest table the cell gathers, 576 blocks of
    128 positions. Mosaic takes the kernel's tiles and fast memory, and the
    program's temporaries stay under twice the chunk's queries and output:
    no ``[H, C, L]`` score tensor reaches HBM."""
    from deeplearning_mpi_tpu.ops.pallas import latent_prefill

    chunk, heads, length = 1024, 64, 576 * 128
    aval = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    attend = functools.partial(latent_prefill.chunk_attention, scale=0.1447, interpret=False)
    with _no_compile_cache():
        compiled = jax.jit(attend).lower(
            aval(chunk, heads, 128), aval(chunk, heads, 64), aval(heads, length, 128), aval(heads, length, 128),
            aval(length, 64), start=aval(dtype=jnp.int32),
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * chunk * heads * (128 + 64 + 128) * 2
