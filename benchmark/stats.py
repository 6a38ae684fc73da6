"""Arithmetic from event lists to metrics; no clock is read here."""

from __future__ import annotations

import statistics
from typing import Any, Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("quantile of nothing")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver's check takes it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def serve_metrics(requests: Sequence[dict[str, Any]], start: float, end: float) -> dict[str, Any]:
    """Client-side serving numbers over the window ``[start, end)``.

    Each request is ``{"due": t, "tokens": [t1, t2, ...]}``: when it was due
    (open loop) and when the client saw each output token. TTFT is timed from
    the due time over EVERY request due in the window; one with no first token
    is counted in ``failed`` and leaves the mean's count. Gaps and the token
    rate take every token the client saw inside the window.
    """
    due = [r for r in requests if start <= r["due"] < end]
    ttft = [r["tokens"][0] - r["due"] for r in due if r["tokens"]]
    gaps = [
        b - a for r in requests for a, b in zip(r["tokens"], r["tokens"][1:]) if start <= b < end
    ]
    emitted = sum(start <= t < end for r in requests for t in r["tokens"])
    out: dict[str, Any] = {
        "attempted": len(due),
        "failed": len(due) - len(ttft),
        "tokens": emitted,
        "gaps": len(gaps),
        "out_tokens_per_s": emitted / (end - start),
    }
    if ttft:
        out["ttft_mean_ms"] = 1e3 * statistics.fmean(ttft)
        out["ttft_p50_ms"] = 1e3 * quantile(ttft, 0.5)
        out["ttft_p90_ms"] = 1e3 * quantile(ttft, 0.9)
    if gaps:
        out["itl_p50_ms"] = 1e3 * quantile(gaps, 0.5)
        out["itl_p95_ms"] = 1e3 * quantile(gaps, 0.95)
    return out
