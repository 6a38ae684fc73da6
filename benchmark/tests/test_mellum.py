"""``mellum-serve-mixed`` (PR 36): the manifest's new entries, Mellum 2's
configuration against the catalog's numbers, its cost functions against hand
counts, the reader of its per-layer metrics on a made-up trace, and the cell
itself through ``serve_sessions`` at test size on the CPU. Nothing is timed."""

import json
import types

import pytest

from benchmark import run as harness
from benchmark.manifest import ROOT, Manifest
from benchmark.mellum import costs, weights
from benchmark.readers import mellum_work, serve_spans
from benchmark.tests import tiny, tiny_sessions

CELL, CONFIG, TRAFFIC = "mellum-serve-mixed", "mellum2-12b-a2.5b-l8", "mixed-sessions-1k-56k"
MELLUM = json.loads((ROOT / f"benchmark/configs/{CONFIG}.json").read_text())
METRICS = {
    "mellum_serve_mfu", "mellum_decode_roofline", "mellum_moe_roofline", "mellum_experts_touched_share", "mellum_full_table_live_share",
    "mellum_window_kv_share", "mellum_engine_step_ms_p50", "mellum_engine_host_ms_per_step_p50", "mellum_serve_device_idle_share",
}
#: toy widths in float32 (a bf16 rounding at toy widths flips an expert every few tokens), the published layer pattern
TOY = {
    "torch_dtype": "float32", "num_hidden_layers": 8, "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "rope_parameters": {
        "full_attention": {**MELLUM["rope_parameters"]["full_attention"], "factor": 4, "original_max_position_embeddings": 32},
        "sliding_attention": MELLUM["rope_parameters"]["sliding_attention"],
    },
}


def test_the_manifest_holds_the_cell_and_appends_only():
    m = Manifest()
    assert m.cells[CELL] == {**m.cells[CELL], "config": CONFIG, "traffic": TRAFFIC, "chips": 1}
    assert list(m.cells)[-1] == CELL and list(m.configs)[-1] == CONFIG and list(m.per_layer)[-9:] == [
        "mellum_serve_mfu", "mellum_decode_roofline", "mellum_moe_roofline", "mellum_experts_touched_share",
        "mellum_full_table_live_share", "mellum_window_kv_share", "mellum_engine_step_ms_p50",
        "mellum_engine_host_ms_per_step_p50", "mellum_serve_device_idle_share",
    ]
    assert m.cell_end_to_end(CELL) == ["serve_itl_p95_ms", "setup_s"] and m.end_to_end["serve_itl_p95_ms"]["workloads"][-1] == CELL
    assert set(m.cell_per_layer(CELL)) == METRICS | {"compile_s"}
    assert all(m.per_layer[name]["workloads"] == [CELL] and m.per_layer[name]["moves"] == "serve_itl_p95_ms" for name in METRICS)
    traffic = m.traffic(TRAFFIC)
    assert traffic["driver"] == "serve_sessions" and len(traffic["prompt_tokens"]) == 16 and sum(traffic["prompt_tokens"]) == 264_192
    assert max(traffic["prompt_tokens"]) == 57_344 and min(traffic["prompt_tokens"]) == 1024 and "probe_after_close" not in traffic
    limits = m.cell_file(CELL)["limits"]
    assert {k: v for k, v in limits.items() if not k.startswith("served_logit_gap")} == {
        "requests_without_first_token": 0, "compiles_in_window": 0, "sessions_ended_or_evicted_before_close": 0, "served_tokens_short_of_200": 0,
    }
    # the expert layer flips on a bf16 rounding, so the widest gap of a sound run swings 0.12-0.37 and overlaps two planted
    # faults: the MEAN over the judged tokens decides (PERF.md section 2), the widest is a backstop for one token gone wrong
    assert 0 < limits["served_logit_gap_mean"] < 0.08 and 0.37 < limits["served_logit_gap"] <= 1.5


def test_the_configuration_is_the_catalog_row_but_for_its_depth():
    assert MELLUM["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types"] and MELLUM["num_hidden_layers"] == 8
    assert MELLUM["layer_types"] == ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2
    assert MELLUM["mlp_layer_types"] == ["sparse"] * 8
    published = {
        "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 7168,
        "moe_intermediate_size": 896, "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": True, "vocab_size": 98304,
        "tie_word_embeddings": False, "rms_norm_eps": 1e-06, "max_position_embeddings": 131072, "sliding_window": 1024,
        "use_sliding_window": True, "attention_bias": False, "hidden_act": "silu", "max_window_layers": 0, "model_type": "mellum",
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
                "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        },
    }
    assert {k: MELLUM[k] for k in published} == published
    assert {"rope", "window", "qk_norm", "experts", "mtp_head", "initializer", "torch_dtype", "engine"} <= set(MELLUM["assumed"])
    assert set(MELLUM["reduced_why"]) == set(MELLUM["reduced"]) and MELLUM["modules"] == "mellum" and "stands_for" in MELLUM
    engine = MELLUM["engine"]
    assert engine["max_slots"] == 16 and engine["block_size"] == 128 and engine["prefill_chunk"] == 1024
    assert engine["max_blocks_per_seq"] * engine["block_size"] == 65_536 > 57_344 + 4096
    assert (engine["num_blocks"] - 1) * 128 >= 264_192 + 16 * 4096  # the prompts, and room for every row to decode a window's steps
    assert 16 * 17 <= engine["window_num_blocks"] - 1 < 264_192 // 128 // 4  # every row's chunk fits; the prompts would not, by far


def test_mellum_parameters_by_hand():
    assert costs.attention_params(MELLUM) == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert costs.expert_params(MELLUM) == 3 * 2304 * 896 == 6_193_152
    assert costs.layer_params(MELLUM) == 21_233_664 + 2304 * 64 + 64 * 6_193_152 + 2 * 2304 == 417_747_456  # 417.7M: 0.835 GB
    assert costs.total_params(MELLUM) == 8 * 417_747_456 + 2 * 98_304 * 2304 + 2304 == 3_794_966_784  # 3,794.9M: 7.59 GB
    tree, _ = weights.flat_shapes(MELLUM)
    assert sum(int(__import__("math").prod(shape)) for _, shape, _ in tree) == costs.total_params(MELLUM)
    assert costs.layer_counts(MELLUM) == (2, 6) and costs.kv_row_bytes(MELLUM) == 2048


def test_a_mellum_decode_step_by_hand():
    contexts, touched = [500, 30_000], 400.0
    rows = 2 * (500 + 30_000) + 6 * (500 + 1024)  # full layers every row, window layers at most 1,024
    assert costs.attended_rows(MELLUM, contexts) == rows
    assert costs.attention_cost(MELLUM, contexts) == (4 * rows * 32 * 128, rows * 2048)
    moe_flops, moe_bytes = costs.moe_cost(MELLUM, 2, touched)
    assert moe_flops == 8 * 2 * 8 * 2 * 6_193_152 and moe_bytes == 400 * 6_193_152 * 2
    shared = 8 * (21_233_664 + 2304 * 64) + 98_304 * 2304
    flops, nbytes = costs.decode_step_cost(MELLUM, contexts, touched)
    assert flops == 2 * shared * 2 + 4 * rows * 32 * 128 + moe_flops and nbytes == 2 * shared + rows * 2048 + moe_bytes


class _Trace:
    def __init__(self, modules, ops):
        self.modules, self.ops = modules, ops

    def module_durations(self, pattern):
        return self.modules

    def op_seconds(self, pattern):
        return self.ops.get(pattern, 0.0)


def test_the_mellum_reader_on_a_made_up_trace(monkeypatch):
    contexts = [2000] * 8 + [40_000] * 8
    run = types.SimpleNamespace(
        config=MELLUM, work=[{"decode": contexts, "prefill": []}] * 4, trace_dir="unused",
        counters={"serve_decode_steps": 10.0, "serve_moe_experts_touched": 4400.0},
    )
    trace, kind = _Trace([0.020] * 5, {"moe": 0.006 * 5}), "TPU v5 lite"
    flops, nbytes = costs.decode_step_cost(MELLUM, contexts, 440.0)
    spec = {"decode_programs": "x", "what": "mfu"}
    assert mellum_work.read(run, trace, spec, kind) == pytest.approx(100 * flops / (0.020 * 197e12))
    assert mellum_work.read(run, trace, spec | {"what": "decode_roofline"}, kind) == pytest.approx(100 * (nbytes / 819e9) / 0.020)
    _, moe_bytes = costs.moe_cost(MELLUM, 16, 440.0)
    assert mellum_work.read(run, trace, spec | {"what": "moe_roofline", "ops": "moe"}, kind) == pytest.approx(100 * (moe_bytes / 819e9) / 0.006)
    assert mellum_work.read(run, trace, spec | {"what": "moe_roofline", "ops": "absent"}, kind) is None
    # the launch spans' labels: blocks live over blocks gathered, the window group's over what whole tables would hold
    launch = ("serve/decode_launch", 0.0, 1e-3, {"rows": 16, "table_rows": 16, "width": 512, "live": 2600, "window_width": 9, "window_live": 140})
    other = ("serve/token_fetch", 0.0, 1e-3, {})
    steps = [serve_spans.Step(0.0, 1.0, {}, [launch, other])] * 3
    monkeypatch.setattr(serve_spans, "host_side", lambda trace_dir: serve_spans.HostSide(steps, [], []))
    live = {"what": "label_share", "span": "serve/decode_launch", "part": ["live"], "whole": ["table_rows", "width"]}
    assert mellum_work.read(run, trace, live, kind) == pytest.approx(100 * 2600 / (16 * 512))
    assert mellum_work.read(run, trace, live | {"part": ["window_live"], "whole": ["live"]}, kind) == pytest.approx(100 * 140 / 2600)
    # a program without the labels (the parent), the counters or any launch gives nothing and does not raise
    assert mellum_work.read(run, trace, live | {"part": ["absent"]}, kind) is None
    monkeypatch.setattr(serve_spans, "host_side", lambda trace_dir: serve_spans.HostSide([], [], []))
    assert mellum_work.read(run, trace, live, kind) is None
    run.counters = {}
    assert mellum_work.read(run, trace, spec, kind) is None
    for name in ("mellum_full_table_live_share", "mellum_window_kv_share", "mellum_moe_roofline", "mellum_serve_mfu", "mellum_decode_roofline"):
        assert Manifest().metric_file(name)["reader"] == "mellum_work"


@pytest.fixture()
def root(tmp_path):
    root = tiny_sessions.make_root(tmp_path, limits={CELL: {"served_logit_gap": 0.1, "served_tokens_short_of_200": 200}})
    path = root / f"benchmark/configs/{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(TOY)
    cfg["engine"]["window_num_blocks"] = 32  # blocks of 8 under a window of 24: 3 rows x 6 in a chunk, 5 in a decode step
    path.write_text(json.dumps(cfg))
    return root


def test_a_sound_run_of_the_cell_is_correct_and_releases(root):
    code, result = harness.run_cell(["--workload", CELL, "--seed", "2147483736", "--seconds", "1.5", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and result["correct"], result["check"]
    assert result["attempted"] == len(tiny_sessions.PROMPTS) and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_itl_p95_ms", "setup_s"}
    assert set(result["check"]) == set(Manifest(root).cell_file(CELL)["limits"])
    assert result["check"]["served_logit_gap"]["value"] < 1e-3  # float32 on both sides at test size


def test_the_faults_tool_reads_mellums_faults(root, monkeypatch, capsys):
    import functools
    import sys

    from benchmark.keye.tools import faults
    from benchmark.mellum import reference

    monkeypatch.setattr(harness, "open_run", functools.partial(harness.open_run, root=root, chips=tiny.cpu_chips))
    monkeypatch.setattr(sys, "argv", ["faults.py", "--workload", CELL, "--seed", "2147483737", "--seconds", "1", "--sessions", "all"])
    assert faults.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert lines[0]["sound"]["correct"]
    assert [line["sessions"] for line in lines[1:]] == ["bfloat16", "float8_e4m3fn", *reference.FAULTS]
    assert all(line["compared"] > 0 and "served_logit_gap" in line["limits"] for line in lines[1:])
    assert not any(line["correct"] for line in lines[2:])  # fp8 and every planted fault lie over the toy limit
