"""``kimi-serve-long``: the manifest's new entries, Kimi-K2.7-Code's
configuration against the catalog's numbers, its cost functions against hand
counts, the reader of its per-layer metrics on a made-up trace, and the cell
itself through ``serve_sessions`` at test size on the CPU. Nothing is timed."""

import json
import math
import types

import pytest

from benchmark import run as harness
from benchmark.kimi import costs, program, weights
from benchmark.manifest import ROOT, Manifest
from benchmark.readers import kimi_work, serve_spans
from benchmark.tests import tiny, tiny_sessions

CELL, CONFIG, TRAFFIC = "kimi-serve-long", "kimi-k2.7-code-l5", "latent-sessions-4k-64k"
KIMI = json.loads((ROOT / f"benchmark/configs/{CONFIG}.json").read_text())
METRICS = {"kimi_serve_mfu", "kimi_decode_roofline", "kimi_latent_attn_roofline", "kimi_experts_touched_share", "kimi_table_live_share"}
#: the catalog's row (the guide's architectures.jsonl, Kimi-K2.7-Code): every number of its ``config``
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 0, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 50000, "routed_scaling_factor": 2.827, "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}
#: toy widths in float32, the published kinds of layer: MLA, a leading dense layer, a share of a sigmoid router's experts
TOY = {
    "torch_dtype": "float32", "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8, "moe_intermediate_size": 16, "n_routed_experts": 4,
    "router_experts": 16, "experts_first": 4, "num_experts_per_tok": 4,
    "rope_scaling": {**KIMI["rope_scaling"], "factor": 4, "original_max_position_embeddings": 32},
}


def test_the_manifest_holds_the_cell_and_appends_only():
    """By name, not by position: a later PR appends its own after these."""
    m = Manifest()
    assert m.cells[CELL] == {**m.cells[CELL], "config": CONFIG, "traffic": TRAFFIC, "chips": 1}
    names = [w["name"] for w in m.doc["workloads"]]
    assert names.index(CELL) > names.index("mellum-serve-mixed")
    assert [c["name"] for c in m.doc["configs"]].index(CONFIG) > [c["name"] for c in m.doc["configs"]].index("mellum2-12b-a2.5b-l8")
    per_layer = [p["name"] for p in m.doc["per_layer"]]
    assert all(per_layer.index(name) > per_layer.index("serve_idle_in_return_share") for name in METRICS)
    assert m.cell_end_to_end(CELL) == ["serve_itl_p95_ms", "setup_s"] and CELL in m.end_to_end["serve_itl_p95_ms"]["workloads"]
    assert set(m.cell_per_layer(CELL)) == METRICS | {"compile_s"}
    assert all(m.per_layer[name]["workloads"] == [CELL] and m.per_layer[name]["moves"] == "serve_itl_p95_ms" for name in METRICS)
    traffic = m.traffic(TRAFFIC)
    assert traffic["driver"] == "serve_sessions" and traffic["prompt_tokens"] == [
        4096, 8192, 8192, 12288, 16384, 16384, 24576, 24576, 32768, 32768, 40960, 40960, 49152, 49152, 57344, 65536,
    ]
    assert sum(traffic["prompt_tokens"]) == 483_328 and "probe_after_close" not in traffic
    assert (traffic["lead_after_first_tokens_s"], traffic["lead_limit_seconds"], traffic["trace_seconds"]) == (2.0, 400.0, 4.0)
    limits = m.cell_file(CELL)["limits"]
    assert {k: v for k, v in limits.items() if not k.startswith("served_logit_gap")} == {
        "requests_without_first_token": 0, "compiles_in_window": 0, "sessions_ended_or_evicted_before_close": 0, "served_tokens_short_of_200": 0,
    }
    # the mean over the judged tokens decides (PERF.md section 2): sound runs read 0.0018-0.0065, the routed scale left
    # out 0.042-0.047 and the fp8 control 0.28-0.31; the widest is a backstop for single tokens (sound runs up to 0.78)
    assert 0.0065 < limits["served_logit_gap_mean"] < 0.0415 and 0.78 < limits["served_logit_gap"] < 2.5


def test_the_configuration_is_the_catalog_row_but_for_what_it_reduces():
    assert KIMI["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: KIMI[k] for k in PUBLISHED if k not in KIMI["reduced"]} == {k: v for k, v in PUBLISHED.items() if k not in KIMI["reduced"]}
    assert (KIMI["num_hidden_layers"], KIMI["n_routed_experts"], KIMI["vocab_size"]) == (5, 12, 163_840 // 8)
    # the share: the router keeps its published width, this chip holds experts 0..11, 32 chips share a layer
    assert (KIMI["router_experts"], KIMI["experts_first"], KIMI["chips_sharing_a_layer"]) == (384, 0, 32) == (PUBLISHED["n_routed_experts"], 0, 384 // 12)
    assert set(KIMI["reduced_why"]) == set(KIMI["reduced"]) and KIMI["modules"] == "kimi" and "stands_for" in KIMI
    assert {"rope", "softmax_scale", "attention", "experts", "bias", "initializer", "torch_dtype", "engine"} <= set(KIMI["assumed"])
    engine = KIMI["engine"]
    assert engine["max_slots"] == 16 and engine["block_size"] == 128 and engine["prefill_chunk"] == 1024
    assert engine["max_blocks_per_seq"] * 128 == 73_728 >= 65_536 + 8192
    assert (engine["num_blocks"] - 1) * 128 >= 483_328 + 16 * 4096  # the prompts, and 4,096 decoded tokens a row
    model = program.model_config(KIMI)
    assert (model.moe_experts, model.moe_router_width, model.moe_first_expert, model.first_dense_layers) == (12, 384, 0, 1)
    assert model.rms_norm_eps == 1e-5 and model.softmax_scale == pytest.approx(0.144680, abs=1e-6)


def test_kimi_parameters_by_hand():
    attention = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 64 * 128 * 7168
    assert costs.attention_params(KIMI) == attention == 101_122_048
    assert costs.expert_params(KIMI) == 3 * 7168 * 2048 == 44_040_192
    dense = attention + 3 * 7168 * 18432 + 2 * 7168 + 1536 + 512
    experts = attention + 7168 * 384 + 384 + 13 * 44_040_192 + 2 * 7168 + 1536 + 512  # 12 held + 1 shared
    vocabulary = 2 * 20_480 * 7168
    assert costs.total_params(KIMI) == dense + 4 * experts + vocabulary + 7168 == 3_496_763_904  # 6.99 GB in bf16
    tree, _ = weights.flat_shapes(KIMI)
    assert sum(math.prod(shape) for _, shape, _ in tree) == costs.total_params(KIMI)
    assert {std for name, _, std in tree if name.endswith("router/bias")} == {weights.BIAS_STD}
    assert costs.latent_row_bytes(KIMI) == 1152 and costs.layer_counts(KIMI) == (1, 4)


def test_a_kimi_decode_step_by_hand():
    contexts, touched = [4000, 60_000], 10.0
    positions = 5 * 64_000  # every live latent row of every layer, once
    core = 2 * 64 * (2 * 512 + 64) * positions  # scores over 576, weighted sum over 512
    assert costs.latent_attention_cost(KIMI, contexts) == (core, positions * 1152)
    weights_ = 5 * 101_122_048 + 3 * 7168 * 18432 + 4 * (7168 * 384 + 44_040_192) + 7168 * 20_480
    absorb = 2 * 64 * 512 * (128 + 128)
    claims = 2 * 8 * 4 * 12 / 384  # the rows' claims that land on a held expert, expected
    flops, nbytes = costs.decode_step_cost(KIMI, contexts, touched)
    assert flops == pytest.approx(2 * weights_ * 2 + 5 * 2 * absorb + core + 2 * claims * 44_040_192)
    assert nbytes == (weights_ + 10 * 44_040_192) * 2 + positions * 1152


class _Trace:
    def __init__(self, modules, ops):
        self.modules, self.ops = modules, ops

    def module_durations(self, pattern):
        return self.modules

    def op_seconds(self, pattern):
        return self.ops.get(pattern, 0.0)


def test_the_kimi_reader_on_a_made_up_trace(monkeypatch):
    contexts = [4000] * 8 + [60_000] * 8
    run = types.SimpleNamespace(
        config=KIMI, work=[{"decode": contexts, "prefill": []}] * 4, trace_dir="unused",
        counters={"serve_decode_steps": 10.0, "serve_moe_experts_touched": 140.0},
    )
    trace, kind = _Trace([0.030] * 5, {"latent": 0.012 * 5}), "TPU v5 lite"
    flops, nbytes = costs.decode_step_cost(KIMI, contexts, 14.0)
    spec = {"decode_programs": "x", "what": "mfu"}
    assert kimi_work.read(run, trace, spec, kind) == pytest.approx(100 * flops / (0.030 * 197e12))
    assert kimi_work.read(run, trace, spec | {"what": "decode_roofline"}, kind) == pytest.approx(100 * (nbytes / 819e9) / 0.030)
    core_flops, core_bytes = costs.latent_attention_cost(KIMI, contexts)
    want = 100 * max(core_flops / 197e12, core_bytes / 819e9) / 0.012
    assert kimi_work.read(run, trace, spec | {"what": "latent_attn_roofline", "ops": "latent"}, kind) == pytest.approx(want)
    assert kimi_work.read(run, trace, spec | {"what": "latent_attn_roofline", "ops": "absent"}, kind) is None
    # the launch spans' labels: live latent positions over what the table's rectangle gathers
    launch = ("serve/decode_launch", 0.0, 1e-3, {"rows": 16, "table_rows": 16, "width": 576, "live": 500_000, "gathered": 16 * 576 * 128})
    steps = [serve_spans.Step(0.0, 1.0, {}, [launch, ("serve/token_fetch", 0.0, 1e-3, {})])] * 3
    monkeypatch.setattr(serve_spans, "host_side", lambda trace_dir: serve_spans.HostSide(steps, [], []))
    live = {"what": "label_share", "span": "serve/decode_launch", "part": ["live"], "whole": ["gathered"]}
    assert kimi_work.read(run, trace, live, kind) == pytest.approx(100 * 500_000 / (16 * 576 * 128))
    # a program without the labels (the parent), the counters or any launch gives nothing and does not raise
    assert kimi_work.read(run, trace, live | {"part": ["absent"]}, kind) is None
    run.counters = {}
    assert kimi_work.read(run, trace, spec, kind) is None
    for name in ("kimi_serve_mfu", "kimi_decode_roofline", "kimi_latent_attn_roofline", "kimi_table_live_share"):
        assert Manifest().metric_file(name)["reader"] == "kimi_work"
    assert Manifest().metric_file("kimi_experts_touched_share")["reader"] == "counter_share"


@pytest.fixture()
def root(tmp_path):
    # float32 on both sides: a sound run's gaps are rounding (under 1e-3), the router's bias put in its weights the
    # least of the planted faults at toy widths (0.098 widest, seed 2147483742)
    root = tiny_sessions.make_root(tmp_path, limits={CELL: {"served_logit_gap": 0.05, "served_tokens_short_of_200": 200}})
    path = root / f"benchmark/configs/{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(TOY)
    path.write_text(json.dumps(cfg))
    return root


def test_a_sound_run_of_the_cell_is_correct(root):
    code, result = harness.run_cell(["--workload", CELL, "--seed", "2147483741", "--seconds", "1.5", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and result["correct"], result["check"]
    assert result["attempted"] == len(tiny_sessions.PROMPTS) and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_itl_p95_ms", "setup_s"}
    assert set(result["check"]) == set(Manifest(root).cell_file(CELL)["limits"])
    assert result["check"]["served_logit_gap"]["value"] < 1e-3  # float32 on both sides at test size


def test_the_faults_tool_reads_kimis_faults(root, monkeypatch, capsys):
    import functools
    import sys

    from benchmark.keye.tools import faults
    from benchmark.kimi import reference

    monkeypatch.setattr(harness, "open_run", functools.partial(harness.open_run, root=root, chips=tiny.cpu_chips))
    monkeypatch.setattr(sys, "argv", ["faults.py", "--workload", CELL, "--seed", "2147483742", "--seconds", "1", "--sessions", "all"])
    assert faults.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert lines[0]["sound"]["correct"]
    assert [line["sessions"] for line in lines[1:]] == ["bfloat16", "float8_e4m3fn", *reference.FAULTS]
    assert all(line["compared"] > 0 and "served_logit_gap" in line["limits"] for line in lines[1:])
    assert not any(line["correct"] for line in lines[2:])  # fp8 and every planted fault lie over the toy limit
