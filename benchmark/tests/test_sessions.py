"""The session cells (PR 34): the manifest's new entries, Keye-VL-2.0's cost
functions against hand counts, the session driver and the plain reference at
test size on the CPU, and the reader of the new per-layer metrics on a made-up
trace. Nothing here is timed."""

import json
import types

import numpy as np
import pytest

from benchmark import check
from benchmark import run as harness
from benchmark.keye import costs, reference, weights
from benchmark.manifest import ROOT, Manifest
from benchmark.readers import keye_work
from benchmark.tests import tiny, tiny_sessions

KEYE = json.loads((ROOT / "benchmark/configs/keye-vl-2.0-30b-a3b-l6.json").read_text())
NEW_CELLS = {"keye-serve-long": "keye-vl-2.0-30b-a3b-l6", "lm-serve-long": "mistral-7b-v0.1-d8"}
LIMITS = {cell: {"served_logit_gap": 0.1, "served_tokens_short_of_200": 200} for cell in NEW_CELLS}
EXACT = {"requests_without_first_token", "compiles_in_window", "sessions_ended_or_evicted_before_close"}


def test_the_manifest_holds_the_two_session_cells():
    m = Manifest()
    for cell, config in NEW_CELLS.items():
        assert m.cells[cell]["config"] == config and m.cells[cell]["chips"] == 1
        assert m.cell_end_to_end(cell) == ["serve_itl_p95_ms", "setup_s"]
        assert m.traffic(m.cells[cell]["traffic"])["driver"] == "serve_sessions"
        assert EXACT | {"served_tokens_short_of_200"} < set(m.cell_file(cell)["limits"])
    assert set(m.cell_file("lm-serve-long")["limits"]) - EXACT == {"served_logit_gap", "served_tokens_short_of_200"}
    # a kept key that flips on a rounding moves single tokens of the selecting model by as much as a fault does: its cell
    # limits a statistic over the judged tokens, the probe that selection cannot move, and the pooled indexer keys
    keye = set(m.cell_file("keye-serve-long")["limits"]) - EXACT
    assert "served_logit_gap" not in keye and {"index_key_gap", "served_tokens_short_of_200"} < keye
    assert keye & {"served_logit_gap_p50", "served_logit_gap_mean"} and keye & {"probe_logit_gap", "probe_logit_gap_p50", "probe_logit_gap_mean"}
    probe = m.traffic("long-sessions-16k-48k")["probe_after_close"]
    assert probe["prompt_tokens"] + probe["new_tokens"] < KEYE["sa_config"]["topk"]
    # compile_s lists no cells and moves setup_s, so it is read in every cell, these two included, from the file it had
    assert "workloads" not in m.per_layer["compile_s"] and "long_compile_s" not in m.per_layer
    both = {
        "compile_s", "long_serve_device_idle_share", "long_engine_host_ms_per_step_p50", "long_engine_step_ms_p50",
        "long_engine_token_fetch_wait_ms_p50", "long_serve_idle_in_launch_share", "long_serve_idle_in_retire_share",
    }
    assert set(m.cell_per_layer("keye-serve-long")) == both | {
        "keye_serve_mfu", "keye_decode_roofline", "keye_topk_sort_share", "keye_moe_roofline", "keye_experts_touched_share",
        "keye_selected_key_share",
    }
    assert set(m.cell_per_layer("lm-serve-long")) == both | {"long_serve_mfu", "long_decode_roofline"}


def test_the_configuration_is_the_catalog_row_but_for_its_depth():
    assert KEYE["reduced"] == ["num_hidden_layers"] and KEYE["num_hidden_layers"] == 6
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts": 128, "num_experts_per_tok": 8, "norm_topk_prob": True, "vocab_size": 151936,
        "tie_word_embeddings": False, "rope_theta": 10000000, "rms_norm_eps": 1e-06, "max_position_embeddings": 262144,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    }
    assert {k: KEYE[k] for k in published} == published
    assert {"qk_norm", "rope", "indexer", "selection", "experts", "initializer", "vision_tower", "engine"} <= set(KEYE["assumed"])
    engine = KEYE["engine"]
    assert (engine["num_blocks"] - 1) * engine["block_size"] >= 327_680 and engine["max_blocks_per_seq"] * engine["block_size"] == 65_536
    assert engine["max_slots"] == 8 and 512 <= engine["prefill_chunk"] <= 2048


def test_keye_parameters_by_hand():
    assert costs.attention_params(KEYE) == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert costs.indexer_params(KEYE) == 2048 * 1024 + 2048 * 64 + 2048 * 16 == 2_260_992
    assert costs.expert_params(KEYE) == 3 * 2048 * 768 == 4_718_592
    layer = 18_874_368 + 2_260_992 + 2048 * 128 + 128 * 4_718_592 + (2 * 2048 + 2 * 128 + 64)
    assert costs.total_params(KEYE) == 6 * layer + 2 * 151_936 * 2048 + 2048  # 4.375B: 8.75 GB in bf16
    assert abs(costs.total_params(KEYE) * 2 - 8.75e9) < 5e6
    tree, _ = weights.flat_shapes(KEYE)
    assert sum(int(np.prod(shape)) for _, shape, _ in tree) == costs.total_params(KEYE)


def test_a_keye_decode_step_by_hand():
    contexts, touched = [1000, 30_000], 100.0
    live, kept = 31_000, 1000 + 2048
    sel_flops, sel_bytes = costs.select_cost(KEYE, contexts)
    assert sel_flops == 6 * (2 * live * 16 * 64 + 4 * kept * 32 * 128)
    assert sel_bytes == 6 * (live * 128 + kept * 2048)  # an indexer key is 128 B, a K/V row 2 KB, a layer
    moe_flops, moe_bytes = costs.moe_cost(KEYE, 2, touched)
    assert moe_flops == 6 * 2 * 8 * 2 * 4_718_592 and moe_bytes == 100 * 4_718_592 * 2
    shared = 6 * (18_874_368 + 2_260_992 + 262_144) + 151_936 * 2048
    flops, nbytes = costs.decode_step_cost(KEYE, contexts, touched)
    assert flops == 2 * shared * 2 + sel_flops + moe_flops and nbytes == 2 * shared + sel_bytes + moe_bytes


@pytest.fixture()
def root(tmp_path):
    return tiny_sessions.make_root(tmp_path, limits=LIMITS)


@pytest.mark.parametrize("cell", list(NEW_CELLS))
def test_a_sound_run_of_a_session_cell_is_correct(root, cell):
    code, result = harness.run_cell(["--workload", cell, "--seed", "2147483699", "--seconds", "1.5", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and result["correct"], result["check"]
    assert result["attempted"] == len(tiny_sessions.PROMPTS) and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_itl_p95_ms", "setup_s"}
    assert set(result["check"]) == set(Manifest(root).cell_file(cell)["limits"])


def test_the_probe_and_the_pooled_keys_are_judged_in_the_selecting_cell(root):
    code, result = harness.run_cell(["--workload", "keye-serve-long", "--seed", "2147483701", "--seconds", "1", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and result["correct"], result["check"]
    assert {"probe_logit_gap_mean", "index_key_gap"} < set(result["check"])
    assert 0 <= result["check"]["index_key_gap"]["value"] < 1e-5  # float32 on both sides at test size


def test_indexer_keys_never_written_make_the_run_not_correct(root, monkeypatch):
    """The fault only the program can have: a stretch of one session's
    positions whose indexer keys never reach the pool (zeros, as the pool
    starts). Planted where the driver reads the pool back."""
    from benchmark.drivers import serve_sessions

    read = serve_sessions.pooled_index_keys

    def skipped(engine, req):
        k, v, index = engine._kv
        engine._kv = (k, v, index.at[:, np.asarray(req.blocks[2:4])].set(0))
        return read(engine, req)

    monkeypatch.setattr(serve_sessions, "pooled_index_keys", skipped)
    code, result = harness.run_cell(["--workload", "keye-serve-long", "--seed", "2147483701", "--seconds", "1", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and not result["correct"]
    assert result["check"]["index_key_gap"]["value"] == pytest.approx(1.0) and not result["check"]["index_key_gap"]["ok"]


def test_the_faults_tool_holds_every_way_to_the_cells_limits(root, monkeypatch, capsys):
    import functools
    import sys

    from benchmark.keye.tools import faults

    monkeypatch.setattr(harness, "open_run", functools.partial(harness.open_run, root=root, chips=tiny.cpu_chips))
    monkeypatch.setattr(sys, "argv", ["faults.py", "--workload", "keye-serve-long", "--seed", "2147483702", "--seconds", "1", "--sessions", "float8_e4m3fn", "--probe", "half_topk,dense_attention", "--first_sessions", "2"])
    assert faults.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert lines[0]["sound"]["correct"] and set(lines[0]["sound"]["check"]) >= {"probe_logit_gap_mean", "index_key_gap"}
    assert [(line.get("sessions"), line.get("probe")) for line in lines[1:]] == [("float8_e4m3fn", None), (None, "half_topk"), (None, "dense_attention")]
    assert lines[1]["compared"] > 0 and "served_logit_gap" in lines[1]["limits"]
    assert lines[3]["widest"] == 0.0 and lines[3]["correct"]  # under the top-k every key is kept: the probe cannot see selection left out


def test_the_traffic_files_engine_keys_override_the_configurations():
    from benchmark.drivers import serve_sessions

    m = Manifest()
    run = types.SimpleNamespace(config=m.config("mistral-7b-v0.1-d8"), traffic=m.traffic("long-sessions-8k-12k"))
    settings = serve_sessions.engine_settings(run)
    assert settings["max_blocks_per_seq"] == 1152 and settings["num_blocks"] == 8192 and "why" not in settings
    prompts = run.traffic["prompt_tokens"]
    assert prompts == list(range(8192, 11777, 512)) and sum(prompts) == 79_872
    assert sum(m.traffic("long-sessions-16k-48k")["prompt_tokens"]) == 249_856


def _toy_keye(root):
    return json.loads((root / "benchmark/configs/keye-vl-2.0-30b-a3b-l6.json").read_text())


def test_the_control_and_every_planted_fault_move_the_keye_reference(root):
    """At toy widths in float32: the reference's own greedy tokens have gap
    0; computed with fp8 operands, or with a fault planted, its tokens lie
    well below the best."""
    import jax.numpy as jnp

    cfg = _toy_keye(root)
    params = weights.build(cfg, weights.seed_words(11), jnp.float32)
    ids = np.random.default_rng(11).integers(0, cfg["vocab_size"], 96).astype(np.int32)
    rows = np.arange(40, 96)
    logits = np.asarray(reference.serve_logits(cfg, params, ids, rows))
    assert check.served_gap(logits, logits.argmax(-1)) == 0.0
    for how in [{"lower": "float8_e4m3fn"}, *({"faults": frozenset([f])} for f in reference.FAULTS)]:
        tokens = np.asarray(reference.serve_logits(cfg, params, ids, rows, **how)).argmax(-1)
        assert check.served_gap(logits, tokens) > 0.1, how
    # a context under the top-k (16), as the probe's: every key is kept, so leaving selection out changes nothing
    # there (the sessions' gap sees it), and half the top-k binds
    short, last = ids[:15], np.arange(6, 15)
    kept = np.asarray(reference.serve_logits(cfg, params, short, last))
    np.testing.assert_array_equal(kept, np.asarray(reference.serve_logits(cfg, params, short, last, faults=frozenset(["dense_attention"]))))
    assert np.abs(kept - np.asarray(reference.serve_logits(cfg, params, short, last, faults=frozenset(["half_topk"])))).max() > 1e-3
    keys = np.asarray(reference.index_keys(cfg, params, ids))
    assert keys.shape == (96, cfg["sa_config"]["indexer_head_dim"]) and np.isfinite(keys).all()


class _Trace:
    def __init__(self, modules, ops):
        self.modules, self.ops = modules, ops

    def module_durations(self, pattern):
        return self.modules

    def op_seconds(self, pattern):
        return self.ops.get(pattern, 0.0)


def test_the_keye_reader_on_a_made_up_trace():
    contexts = [20_000] * 8
    run = types.SimpleNamespace(
        config=KEYE, work=[{"decode": contexts, "prefill": []}] * 4,
        counters={"serve_decode_steps": 10.0, "serve_moe_experts_touched": 3000.0},
    )
    trace = _Trace([0.020] * 5, {"sel": 0.010 * 5, "moe": 0.005 * 5})
    kind = "TPU v5 lite"
    flops, nbytes = costs.decode_step_cost(KEYE, contexts, 300.0)
    spec = {"decode_programs": "x", "what": "mfu"}
    assert keye_work.read(run, trace, spec, kind) == pytest.approx(100 * flops / (0.020 * 197e12))
    assert keye_work.read(run, trace, spec | {"what": "decode_roofline"}, kind) == pytest.approx(100 * (nbytes / 819e9) / 0.020)
    assert keye_work.read(run, trace, spec | {"what": "op_share", "ops": "sel"}, kind) == pytest.approx(100 * 0.010 / 0.020)
    _, moe_bytes = costs.moe_cost(KEYE, 8, 300.0)
    assert keye_work.read(run, trace, spec | {"what": "moe_roofline", "ops": "moe"}, kind) == pytest.approx(100 * (moe_bytes / 819e9) / 0.005)
    # a program without the counters, or a slice without the operations, gives nothing and does not raise
    assert keye_work.read(run, trace, spec | {"what": "moe_roofline", "ops": "absent"}, kind) is None
    run.counters = {}
    assert keye_work.read(run, trace, spec, kind) is None
