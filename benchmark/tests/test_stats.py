import pytest

from benchmark import stats


def _requests(stall: float):
    """Five requests due a second apart, first token 0.1 s after due and one
    token every 0.05 s -- but the third is held ``stall`` seconds before its
    first token and again before its fifth. A sixth never gets a token."""
    out = []
    for i in range(5):
        first = i + 0.1 + (stall if i == 2 else 0.0)
        tokens = [first + 0.05 * k for k in range(10)]
        if i == 2:
            tokens = tokens[:4] + [t + stall for t in tokens[4:]]
        out.append({"due": float(i), "tokens": tokens})
    out.append({"due": 4.5, "tokens": []})
    return out


def test_ttft_is_timed_from_the_due_time_and_a_request_without_a_first_token_is_failed():
    m = stats.serve_metrics(_requests(0.0), 0.0, 10.0)
    assert m["attempted"] == 6 and m["failed"] == 1
    assert m["ttft_mean_ms"] == pytest.approx(100.0)
    assert m["tokens"] == 50 and m["gaps"] == 45
    assert m["out_tokens_per_s"] == pytest.approx(5.0)
    assert m["itl_p95_ms"] == pytest.approx(50.0)


def test_a_stall_moves_the_mean_the_p95_and_the_rate_but_not_the_medians():
    calm, stalled = stats.serve_metrics(_requests(0.0), 0.0, 4.8), stats.serve_metrics(_requests(2.0), 0.0, 4.8)
    assert stalled["ttft_mean_ms"] == pytest.approx(calm["ttft_mean_ms"] + 2000.0 / 5)
    assert stalled["itl_p95_ms"] > 10 * calm["itl_p95_ms"] or stalled["gaps"] < calm["gaps"]
    assert stalled["out_tokens_per_s"] < calm["out_tokens_per_s"]  # tokens pushed past the window's end
    assert stalled["ttft_p50_ms"] == pytest.approx(calm["ttft_p50_ms"])
    assert stalled["itl_p50_ms"] == pytest.approx(calm["itl_p50_ms"])


def test_only_what_lies_in_the_window_counts():
    m = stats.serve_metrics(_requests(0.0), 1.0, 3.0)
    assert m["attempted"] == 2  # due at 1.0 and 2.0
    assert m["tokens"] == 20 and m["out_tokens_per_s"] == pytest.approx(10.0)


def test_quantile_and_spread():
    assert stats.quantile([1, 2, 3, 4, 5], 0.5) == 3 and stats.quantile([0, 10], 0.95) == pytest.approx(9.5)
    assert stats.spread([100, 101, 102, 103, 104, 105]) == pytest.approx((104.25 - 100.75) / 102.5)
