import json

import pytest

from benchmark import costs
from benchmark.manifest import ROOT

D2 = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.1-d2.json").read_text())
D8 = json.loads((ROOT / "benchmark/configs/mistral-7b-v0.1-d8.json").read_text())


def test_mistral_layer_by_hand():
    attention = 4096 * 4096 * 2 + 4096 * 1024 * 2  # q and out at 32 heads, k and v at 8
    mlp = 3 * 4096 * 14336
    assert costs.layer_matmul_params(D2) == attention + mlp == 218_103_808
    assert costs.layer_params(D2) == 218_112_000  # with the two norm scales: 218.1M
    assert costs.total_params(D2) == 2 * 218_112_000 + 2 * 131_072_000 + 4096  # 698M
    assert costs.total_params(D8) == 8 * 218_112_000 + 2 * 131_072_000 + 4096  # 2.0B


def test_windowed_causal_attention_counts_the_masked_in_pairs_only():
    brute = lambda start, n, w: sum(min(p + 1, w) if w else p + 1 for p in range(start, start + n))
    assert costs.attended_pairs(0, 8192, 4096) == brute(0, 8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    assert costs.attended_pairs(0, 8192, 0) == 8192 * 8193 // 2
    for case in [(4000, 512, 4096), (5000, 512, 4096), (10, 5, 3), (0, 3, 8), (3, 4, 3)]:
        assert costs.attended_pairs(*case) == brute(*case)


def test_train_step_and_flash_by_hand():
    pairs = 4096 * 4097 // 2 + 4096 * 4096
    dense = 2 * (2 * 218_103_808 + 131_072_000) * 8192
    attn = 2 * 4 * pairs * 4096
    assert costs.train_step_flops(D2, 1, 8192) == 3 * (dense + attn)
    flops, nbytes = costs.flash_train_cost(D2, 1, 8192)
    assert flops == 3 * attn
    q, kv = 8192 * 4096 * 2, 8192 * 1024 * 2
    assert nbytes == 2 * (6 * q + 6 * kv)
    # compute-bound on a v5e: 2.47 TFLOP at 197 TFLOP/s against 1 GB at 819 GB/s
    assert costs.roofline_seconds(flops, nbytes, "TPU v5 lite") == pytest.approx(flops / 197e12)


def test_decode_step_reads_the_weights_once_and_the_live_kv_rows_once():
    weights = 8 * 218_103_808 + 131_072_000
    flops, nbytes = costs.decode_step_cost(D8, [100, 5000])
    live = 100 + 4096  # the window caps what a row reads
    assert nbytes == 2 * weights + 8 * live * 2 * 8 * 128 * 2
    assert flops == 2 * weights * 2 + 8 * 4 * live * 32 * 128
    assert costs.roofline_seconds(flops, nbytes, "TPU v5 lite") == pytest.approx(nbytes / 819e9)  # bandwidth-bound
    assert costs.prefill_chunk_flops(D8, 512, 512) == 2 * 8 * 218_103_808 * 512 + 2 * 131_072_000 + 8 * 4 * costs.attended_pairs(512, 512, 4096) * 4096


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peak("TPU v9")
