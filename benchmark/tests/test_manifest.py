import json
import shutil
from pathlib import Path

import pytest

from benchmark.manifest import ROOT, Manifest, ManifestError
from benchmark.tests import tiny


def _copy(tmp: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp / "benchmark" / sub)
    return tmp


def _edit(root: Path, change) -> None:
    doc = json.loads((root / "BENCHMARK.json").read_text())
    change(doc)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))


def test_the_committed_manifest_passes_and_every_cell_reads_its_metrics():
    m = Manifest()
    assert m.doc["command"][-1].startswith(m.doc["paths"][0] + "/")
    for cell in m.cells:
        assert "setup_s" in m.cell_end_to_end(cell) and m.cell_per_layer(cell)
        for name in m.cell_per_layer(cell):
            Manifest.reader(m.metric_file(name)["reader"]).read  # its reader exists


def test_a_bad_name_a_long_unit_and_a_metric_whose_cell_lacks_what_it_moves_are_refused(tmp_path):
    root = _copy(tmp_path)
    pristine = (root / "BENCHMARK.json").read_text()
    cases = [
        lambda d: d["workloads"][0].update(name="lm train 8k"),
        lambda d: d["end_to_end"][0].update(unit="tokens per s"),
        lambda d: d["end_to_end"][1].update(bound=0.2),
        lambda d: d["workloads"][0].update(chips=2),
    ]
    for change in cases:
        (root / "BENCHMARK.json").write_text(pristine)
        _edit(root, change)
        with pytest.raises(ManifestError):
            Manifest(root)
    (root / "BENCHMARK.json").write_text(pristine)
    _edit(root, lambda d: d["end_to_end"][0].update(unit="x" * 16))
    Manifest(root)  # 16 characters is the most
    _edit(root, lambda d: d["end_to_end"][0].update(unit="x" * 17))
    with pytest.raises(ManifestError, match="unit"):
        Manifest(root)
    # a per-layer metric read in a cell that does not report the end-to-end metric it moves
    (root / "BENCHMARK.json").write_text(pristine)
    _edit(root, lambda d: d["per_layer"][1].update(moves="serve_itl_p95_ms"))
    spec = root / "benchmark/metrics/train_data_wait_share.json"
    spec.write_text(json.dumps(json.loads(spec.read_text()) | {"moves": "serve_itl_p95_ms"}))
    with pytest.raises(ManifestError, match="does not report serve_itl_p95_ms"):
        Manifest(root)


def test_a_cell_a_configuration_a_traffic_mix_and_a_metric_are_added_by_files_and_entries_alone(tmp_path):
    """What a later PR does: new files, new entries, no file that is there
    edited -- and the harness runs the new cell."""
    root = tiny.make_root(tmp_path, limits={})
    data = root / "benchmark"
    cfg = json.loads((data / "configs/mistral-7b-v0.1-d8.json").read_text())
    cfg["num_hidden_layers"] = 3
    (data / "configs/other-model.json").write_text(json.dumps(cfg))
    traffic = json.loads((data / "traffic/chat-steady-2p4.json").read_text())
    traffic["rate_per_s"] = 6.0
    (data / "traffic/chat-slow.json").write_text(json.dumps(traffic))
    shutil.copy(data / "cells/lm-serve-chat.json", data / "cells/other-serve.json")
    metric = json.loads((data / "metrics/queue_wait_p90_ms.json").read_text())
    metric.update(stat="p50", workloads=["other-serve"])
    (data / "metrics/queue_wait_p50_ms.json").write_text(json.dumps(metric))

    def add(doc):
        doc["configs"].append({"name": "other-model", "source": cfg["source"], "file": "benchmark/configs/other-model.json",
                               "reduced": cfg["reduced"], "why": "a later PR's model"})
        doc["workloads"].append({"name": "other-serve", "config": "other-model", "traffic": "chat-slow", "chips": 1, "why": "a later PR's cell"})
        for m in doc["end_to_end"]:
            if "workloads" in m and "lm-serve-chat" in m["workloads"]:
                m["workloads"].append("other-serve")
        for m in doc["per_layer"]:
            if "workloads" in m and "lm-serve-chat" in m["workloads"]:
                m["workloads"].append("other-serve")
        doc["per_layer"].append({k: metric[k] for k in ("unit", "better", "source", "layer", "moves", "workloads")} | {"name": "queue_wait_p50_ms"})

    _edit(root, add)
    for path in (data / "metrics").glob("*.json"):  # the files state their cells too
        spec = json.loads(path.read_text())
        if "lm-serve-chat" in spec.get("workloads", []):
            spec["workloads"].append("other-serve")
            path.write_text(json.dumps(spec))
    m = Manifest(root)
    assert "queue_wait_p50_ms" in m.cell_per_layer("other-serve")
    assert m.config("other-model")["num_hidden_layers"] == 3 and m.traffic("chat-slow")["rate_per_s"] == 6.0

    from benchmark import run as harness

    code, result = harness.run_cell(["--workload", "other-serve", "--seed", "3", "--seconds", "2", "--trace", "0"], root=root, chips=tiny.cpu_chips)
    assert code == 0 and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_ttft_mean_ms", "serve_itl_p95_ms", "serve_out_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "check" and result["check"]["compiles_in_window"]["ok"]
