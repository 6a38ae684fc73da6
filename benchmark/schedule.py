"""The one general traffic generator for served cells.

A traffic file gives rate, lead, length distributions and ``schedule_seed``;
the arrivals and lengths are drawn from that seed alone, so every ``--seed``
serves the same requests at the same times (``--seed`` makes the token ids and
the weights). Gaps are exponential, then scaled so that the draw's own rate is
the nominal one over the longest window the benchmark allows: a shorter window
is a prefix of the same schedule.
"""

from __future__ import annotations

import json
import math
from typing import Any

import numpy as np

MAX_RUN_SECONDS = 51  # the contract's longest window


def _lengths(rng: np.random.Generator, spec: dict[str, Any], n: int) -> np.ndarray:
    drawn = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(drawn), spec["min"], spec["max"]).astype(np.int64)


def build(traffic: dict[str, Any], seconds: float) -> list[dict[str, Any]]:
    """Requests due in ``[0, lead + seconds)``: ``due`` in seconds from the
    start of the lead, ``prompt_len`` and ``new_tokens``."""
    horizon = traffic["lead_seconds"] + MAX_RUN_SECONDS
    n = int(round(traffic["rate_per_s"] * horizon))
    rng = np.random.default_rng(traffic["schedule_seed"])
    gaps = rng.exponential(1.0, n)
    due = np.cumsum(gaps) * (horizon / gaps.sum()) - gaps[0] * (horizon / gaps.sum()) / 2
    prompts = _lengths(rng, traffic["prompt_tokens"], n)
    outputs = _lengths(rng, traffic["output_tokens"], n)
    end = traffic["lead_seconds"] + seconds
    return [
        {"due": float(t), "prompt_len": int(p), "new_tokens": int(o)}
        for t, p, o in zip(due, prompts, outputs) if t < end
    ]


def dumps(requests: list[dict[str, Any]]) -> bytes:
    return json.dumps(requests, sort_keys=True).encode()


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index`` under ``--seed``; ids differ by request,
    so no two prompts share a prefix."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7, index]))
    return rng.integers(0, vocab, length, dtype=np.int64).astype(np.int32)
