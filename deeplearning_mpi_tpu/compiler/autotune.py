"""Deterministic Pallas block-size autotuner + persistent JSON tuning DB.

The flash kernels ship block-shape defaults from one v5e sweep
(``flash_attention.py``: 1024x1024 was 8.5x faster than the flash-paper
128x128 on that chip) — but the right blocks move with generation, dtype,
and shape, and the decode path additionally has a *schedule* choice (fused
Pallas kernel vs the dense einsum) whose crossover is an empirical fact,
not a constant. This module searches those spaces the boring way:
enumerate candidates in a fixed order, verify each against the dense
oracle, time with median-of-repeats, persist the winner.

DB entries are keyed by ``(kernel, shape, dtype, backend)`` — a tuning
measured on one backend never leaks to another. Call sites
(``ops/pallas/flash_attention.py``, ``ops/pallas/flash_decode.py``,
``ops/attention.py:decode_attention``) consult :func:`default_db` lazily and fall back to the module defaults on any
miss, parse error, or absent DB — tuning is an overlay, never a
requirement.

Determinism: fixed PRNG keys, a fixed candidate enumeration (descending,
so ties break toward the measured-good larger blocks), numerics gated
before timing (a fast-but-wrong candidate is discarded, not preferred),
and median-of-repeats timing. Same machine, same DB.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
import time
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp

from deeplearning_mpi_tpu.resilience.integrity import atomic_write_json

__all__ = [
    "ATTENTION_BLOCK_CANDIDATES",
    "DECODE_BLOCK_CANDIDATES",
    "SPEC_K_CANDIDATES",
    "STEP_REMAT_CANDIDATES",
    "TuningDB",
    "default_db",
    "expected_tokens_per_step",
    "set_default_db",
    "spec_k_key",
    "step_candidates",
    "step_tuning_key",
    "tune_flash_attention",
    "tune_flash_decode",
    "tune_spec_k",
    "tune_step_schedule",
    "tuned_attention_blocks",
    "tuned_decode_schedule",
    "tuned_spec_k",
    "tuned_step_schedule",
    "tuning_key",
]

DB_VERSION = 1
#: Env var naming the tuning DB consulted at kernel call sites.
ENV_DB = "DMT_TUNING_DB"

#: Default search space for flash-attention block shapes (descending: ties
#: resolve toward the larger block, matching the measured preference).
ATTENTION_BLOCK_CANDIDATES = (1024, 512, 256, 128)
#: Default search space for the flash-decode KV block.
DECODE_BLOCK_CANDIDATES = (2048, 1024, 512, 256)
#: Default search space for the speculative proposal depth (0 = plain
#: decode; always a candidate so a hostile draft can lose to no-draft).
SPEC_K_CANDIDATES = (0, 1, 2, 4)


def tuning_key(
    kernel: str, shape: tuple[int, ...], dtype: Any, backend: str
) -> str:
    dims = "x".join(str(int(s)) for s in shape)
    return f"{kernel}|{dims}|{jnp.dtype(dtype).name}|{backend}"


def _mesh_desc(mesh: Any) -> str:
    """Terse mesh descriptor for tuning keys: ``data2`` / ``data2,model2``.
    Accepts a ``jax.sharding.Mesh``, an ``{axis: size}`` dict, or a
    pre-formatted string."""
    if isinstance(mesh, str):
        return mesh
    if isinstance(mesh, dict):
        items = list(mesh.items())
    else:
        items = list(zip(mesh.axis_names, mesh.devices.shape))
    # Canonical: size-1 axes carry no sharding, so they must not fork keys
    # between otherwise-identical meshes (MeshSpec always materializes
    # every axis; a hand-built Mesh may not).
    active = [(a, int(n)) for a, n in items if int(n) > 1]
    if not active:
        return "1"
    return ",".join(f"{a}{n}" for a, n in active)


def step_tuning_key(
    model: str,
    shape: tuple[int, ...],
    mesh: Any,
    dtype: Any,
    backend: str | None = None,
) -> str:
    """Key for a whole-step schedule entry:
    ``step|<model>|<batch>x<seq>|<mesh>|<dtype>|<backend>``.

    A step schedule (remat policy, grad-accum chunking, donation, overlap)
    tuned for one model/shape/mesh/dtype says nothing about another — same
    exact-key-only contract as the kernel entries.
    """
    backend = backend or jax.default_backend()
    dims = "x".join(str(int(s)) for s in shape)
    return (
        f"step|{model}|{dims}|{_mesh_desc(mesh)}|"
        f"{jnp.dtype(dtype).name}|{backend}"
    )


class TuningDB:
    """JSON-backed map from tuning key to winning kernel parameters.

    On-disk format (``docs/COMPILATION.md``)::

        {"version": 1,
         "entries": {"flash_attention|4x4096x8x64|bfloat16|tpu": {
             "kernel": ..., "shape": [...], "dtype": ..., "backend": ...,
             "params": {"block_q": 1024, "block_k": 512},
             "best_seconds": ..., "candidates": [...]}}}

    Writes go through ``resilience.integrity.atomic_write_json`` (tmp +
    fsync + rename), so a crashed tuning run leaves the previous DB, never
    a torn one; :meth:`load` treats a corrupt/missing file as empty for the
    same reason — a tuning DB must never be able to take a run down.
    """

    def __init__(self, path: str | Path | None = None):
        self.path = Path(path) if path else None
        self.entries: dict[str, dict[str, Any]] = {}
        #: provenance of every successful lookup this process made through
        #: this DB (one record per distinct key), so benchmarks can report
        #: exactly which tunings influenced a run (``bench.py`` surfaces it
        #: as ``details.tuning_provenance``).
        self.consulted: list[dict[str, Any]] = []
        self._consulted_keys: set[str] = set()

    @classmethod
    def load(cls, path: str | Path) -> "TuningDB":
        db = cls(path)
        try:
            payload = json.loads(Path(path).read_text())
            if payload.get("version") == DB_VERSION:
                db.entries = dict(payload["entries"])
        except (OSError, ValueError, KeyError, TypeError):
            pass  # absent or corrupt: start empty, keep the path
        return db

    def save(self, path: str | Path | None = None) -> Path:
        path = Path(path) if path else self.path
        if path is None:
            raise ValueError("TuningDB has no path to save to")
        self.path = path
        atomic_write_json(
            path, {"version": DB_VERSION, "entries": self.entries}
        )
        return path

    def record(
        self,
        kernel: str,
        shape: tuple[int, ...],
        dtype: Any,
        params: dict[str, Any],
        *,
        backend: str | None = None,
        best_seconds: float | None = None,
        candidates: list[dict[str, Any]] | None = None,
    ) -> str:
        backend = backend or jax.default_backend()
        key = tuning_key(kernel, shape, dtype, backend)
        self.entries[key] = {
            "kernel": kernel,
            "shape": [int(s) for s in shape],
            "dtype": jnp.dtype(dtype).name,
            "backend": backend,
            "params": dict(params),
            "best_seconds": best_seconds,
            "candidates": candidates or [],
        }
        return key

    def record_key(
        self,
        key: str,
        params: dict[str, Any],
        *,
        best_seconds: float | None = None,
        candidates: list[dict[str, Any]] | None = None,
        **meta: Any,
    ) -> str:
        """Store a winning entry under an arbitrary pre-built key (the
        ``step|...`` whole-step entries use this; kernel entries keep the
        typed :meth:`record`). Extra ``meta`` keyword fields land in the
        entry verbatim."""
        self.entries[key] = {
            "params": dict(params),
            "best_seconds": best_seconds,
            "candidates": candidates or [],
            **meta,
        }
        return key

    def lookup_key(self, key: str) -> dict[str, Any] | None:
        """Params for an exact key, or None; a hit is noted in
        :attr:`consulted` (once per distinct key)."""
        entry = self.entries.get(key)
        if not entry:
            return None
        if key not in self._consulted_keys:
            self._consulted_keys.add(key)
            self.consulted.append({
                "key": key,
                "params": dict(entry["params"]),
                "best_seconds": entry.get("best_seconds"),
            })
        return dict(entry["params"])

    def lookup(
        self,
        kernel: str,
        shape: tuple[int, ...],
        dtype: Any,
        *,
        backend: str | None = None,
    ) -> dict[str, Any] | None:
        """The winning params for this exact (kernel, shape, dtype,
        backend), or None — no nearest-shape guessing; a wrong block size
        can be slower than the default it replaced."""
        backend = backend or jax.default_backend()
        return self.lookup_key(tuning_key(kernel, shape, dtype, backend))

    def __len__(self) -> int:
        return len(self.entries)


# -- process-default DB (what kernel call sites consult) ---------------------

_UNSET = object()
_default_db: Any = _UNSET


def default_db() -> TuningDB | None:
    """The process-wide tuning DB: whatever :func:`set_default_db` installed,
    else ``$DMT_TUNING_DB`` loaded once, else None (kernels keep their
    defaults)."""
    global _default_db
    if _default_db is _UNSET:
        path = os.environ.get(ENV_DB)
        _default_db = TuningDB.load(path) if path else None
    return _default_db


def set_default_db(db: TuningDB | str | Path | None) -> TuningDB | None:
    """Install (or clear, with None) the process-default DB; paths are
    loaded. Returns the installed DB. Passing None re-arms the
    ``$DMT_TUNING_DB`` fallback on the next :func:`default_db` call only if
    the env var is consulted again — i.e. it resets to 'unset'."""
    global _default_db
    if db is None:
        _default_db = _UNSET
        return None
    if not isinstance(db, TuningDB):
        db = TuningDB.load(db)
    _default_db = db
    return db


def _consult(
    kernel: str, shape: tuple[int, ...], dtype: Any
) -> dict[str, Any] | None:
    """Call-site lookup that must never raise: a broken DB degrades to
    'no tuning', not to a failed forward pass."""
    try:
        db = default_db()
        if db is None:
            return None
        return db.lookup(kernel, shape, dtype)
    except Exception:
        return None


def tuned_attention_blocks(
    shape: tuple[int, ...], dtype: Any
) -> tuple[int, int] | None:
    """``(block_q, block_k)`` for a ``[B, S, H, D]`` flash-attention call,
    or None when untuned."""
    params = _consult("flash_attention", shape, dtype)
    if not params:
        return None
    try:
        return int(params["block_q"]), int(params["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def tuned_decode_schedule(
    shape: tuple[int, ...], dtype: Any
) -> dict[str, Any] | None:
    """``{"schedule": "kernel"|"einsum", "block": int|None}`` for a
    ``[B, L, Hkv, D]`` contiguous decode buffer
    (``ops.attention.decode_attention(use_kernel=None)``), or None when
    untuned."""
    params = _consult("flash_decode", shape, dtype)
    if not params or params.get("schedule") not in ("kernel", "einsum"):
        return None
    return params


# -- speculative proposal depth -----------------------------------------------

def spec_k_key(
    config: Any, draft_layers: int, dtype: Any, backend: str | None = None
) -> str:
    """Key for a tuned speculative depth:
    ``spec_k|<layers>x<heads>x<head_dim>x<d_model>|draft<N>|<dtype>|<backend>``.
    The winner depends on the target/draft cost ratio and the acceptance
    rate — all functions of the two architectures, so the key carries the
    target dims and the draft depth."""
    backend = backend or jax.default_backend()
    dims = (
        f"{config.num_layers}x{config.num_heads}x{config.head_dim}"
        f"x{config.d_model}"
    )
    return f"spec_k|{dims}|draft{int(draft_layers)}|{jnp.dtype(dtype).name}|{backend}"


def tuned_spec_k(
    config: Any, draft_layers: int, dtype: Any
) -> dict[str, Any] | None:
    """The tuned ``{"spec_k": int, "accept_rate": float}`` for this
    target/draft pair, or None when untuned — never raises."""
    try:
        db = default_db()
        if db is None:
            return None
        params = db.lookup_key(spec_k_key(config, draft_layers, dtype))
        if not params or not isinstance(params.get("spec_k"), int):
            return None
        return params
    except Exception:
        return None


def expected_tokens_per_step(accept_rate: float, k: int) -> float:
    """Expected emitted tokens per verify step under per-proposal
    acceptance probability ``a``: ``E = (1 - a^(k+1)) / (1 - a)`` (the
    truncated geometric series — each extra proposal only pays off if the
    whole prefix before it matched). The analytic half of the spec-k
    tradeoff; :func:`tune_spec_k` measures the other half (draft + verify
    step costs) empirically."""
    a = min(max(float(accept_rate), 0.0), 1.0)
    if a >= 1.0:
        return float(k + 1)
    return (1.0 - a ** (k + 1)) / (1.0 - a)


# -- measurement -------------------------------------------------------------

def measure(
    fn: Callable[..., Any], *args: Any, repeats: int = 3, warmup: int = 1
) -> float:
    """Median wall-seconds per call, fully synchronized. The first
    (warmup) calls absorb compilation so block-shape timings compare
    steady-state execution, which is what the serving/training hot loops
    see."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _allclose(a: jax.Array, b: jax.Array, dtype: Any) -> bool:
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-5
    return bool(
        jnp.allclose(
            a.astype(jnp.float32), b.astype(jnp.float32),
            rtol=tol, atol=tol,
        )
    )


def _rejection(
    fn: Callable[..., Any], args: tuple, oracle: jax.Array, dtype: Any
) -> dict[str, str] | None:
    """Why a candidate may not compete, or ``None``. A candidate the
    compiler refuses (Mosaic's scoped-VMEM limit, a layout it cannot infer)
    is recorded as ``"compile"`` with the reason — told apart from wrong
    numerics and from merely slower — and does not end the search."""
    try:
        out = jax.block_until_ready(fn(*args))
    except Exception as err:  # noqa: BLE001 — jaxlib/Mosaic raise their own types
        return {"rejected": "compile", "error": " ".join(str(err).split())[:300]}
    if not _allclose(out, oracle, dtype):
        return {"rejected": "numerics"}
    return None


# -- flash attention ---------------------------------------------------------

def attention_candidates(
    seq: int, candidates: tuple[int, ...] | None = None
) -> list[tuple[int, int]]:
    """Legal ``(block_q, block_k)`` pairs for ``seq``, in the fixed
    (descending) search order."""
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import usable_blocks

    cand = tuple(
        sorted(set(candidates or ATTENTION_BLOCK_CANDIDATES), reverse=True)
    )
    return [
        (bq, bk)
        for bq in cand
        for bk in cand
        if bq <= seq and bk <= seq and usable_blocks(bq, bk, seq)
    ]


def tune_flash_attention(
    shape: tuple[int, int, int, int],
    dtype: Any = jnp.float32,
    *,
    db: TuningDB | None = None,
    candidates: tuple[int, ...] | None = None,
    repeats: int = 3,
    causal: bool = True,
    interpret: bool | None = None,
) -> dict[str, Any]:
    """Search flash-attention block shapes for one ``[B, S, H, D]`` shape.

    Every candidate is verified against ``dense_attention`` (the oracle the
    kernel's tests use) before it may win — a mis-tiled candidate that
    returns garbage fast is discarded, not selected. Returns the winning
    ``{"block_q", "block_k"}`` (recorded into ``db`` when given), or ``{}``
    when no candidate legally tiles the shape.
    """
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )
    from deeplearning_mpi_tpu.ops.attention import dense_attention

    batch, seq, heads, head_dim = shape
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)
    oracle = dense_attention(q, k, v, causal=causal)

    results: list[dict[str, Any]] = []
    best: dict[str, Any] | None = None
    for bq, bk in attention_candidates(seq, candidates):
        fn = jax.jit(
            lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret,
            )
        )
        rejected = _rejection(fn, (q, k, v), oracle, dtype)
        if rejected:
            results.append({"block_q": bq, "block_k": bk, **rejected})
            continue
        secs = measure(fn, q, k, v, repeats=repeats)
        entry = {"block_q": bq, "block_k": bk, "seconds": secs}
        results.append(entry)
        if best is None or secs < best["seconds"]:
            best = entry
    if best is None:
        return {}
    params = {"block_q": best["block_q"], "block_k": best["block_k"]}
    if db is not None:
        db.record(
            "flash_attention", shape, dtype, params,
            best_seconds=best["seconds"], candidates=results,
        )
    return params


# -- flash decode ------------------------------------------------------------

def tune_flash_decode(
    shape: tuple[int, int, int, int],
    dtype: Any = jnp.float32,
    *,
    heads: int | None = None,
    db: TuningDB | None = None,
    blocks: tuple[int, ...] | None = None,
    repeats: int = 3,
    interpret: bool | None = None,
) -> dict[str, Any]:
    """Search the decode schedule (einsum vs Pallas kernel) and the
    kernel's KV block for one ``[B, L, Hkv, D]`` buffer shape.

    The einsum schedule (``batched_decode_attention``, the
    read-everything path) is always a candidate AND the numerics oracle;
    kernel candidates must match it to compete. Returns
    the winning ``{"schedule", "block"}`` (recorded into ``db``).
    """
    from deeplearning_mpi_tpu.ops.attention import batched_decode_attention
    from deeplearning_mpi_tpu.ops.pallas.flash_decode import (
        decode_block_fits,
        flash_decode,
    )

    batch, length, kv_heads, head_dim = shape
    heads = heads or kv_heads
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (batch, 1, heads, head_dim), dtype)
    k_buf = jax.random.normal(kk, shape, dtype)
    v_buf = jax.random.normal(kv, shape, dtype)
    # Deterministic spread of fill levels — the continuous-batching regime
    # (every slot at its own depth) the schedule choice must serve.
    index = jnp.asarray(
        [length - 1 - (i * (length // 2)) // max(batch - 1, 1)
         for i in range(batch)],
        jnp.int32,
    )

    einsum_fn = jax.jit(
        lambda q, k_buf, v_buf, index: batched_decode_attention(
            q, k_buf, v_buf, index
        )
    )
    oracle = einsum_fn(q, k_buf, v_buf, index)
    results = [{
        "schedule": "einsum", "block": None,
        "seconds": measure(einsum_fn, q, k_buf, v_buf, index,
                           repeats=repeats),
    }]
    best = results[0]

    seen: set[int] = set()
    for want in sorted(
        set(blocks or DECODE_BLOCK_CANDIDATES), reverse=True
    ):
        fitted = decode_block_fits(want, length)
        if fitted is None or fitted in seen:
            continue
        seen.add(fitted)
        fn = jax.jit(
            lambda q, k_buf, v_buf, index, b=fitted: flash_decode(
                q, k_buf, v_buf, index, block=b, interpret=interpret
            )
        )
        rejected = _rejection(fn, (q, k_buf, v_buf, index), oracle, dtype)
        if rejected:
            results.append(
                {"schedule": "kernel", "block": fitted, **rejected}
            )
            continue
        secs = measure(fn, q, k_buf, v_buf, index, repeats=repeats)
        entry = {"schedule": "kernel", "block": fitted, "seconds": secs}
        results.append(entry)
        if secs < best["seconds"]:
            best = entry
    params = {"schedule": best["schedule"], "block": best["block"]}
    if db is not None:
        db.record(
            "flash_decode", shape, dtype, params,
            best_seconds=best["seconds"], candidates=results,
        )
    return params


# -- speculative depth search -------------------------------------------------

def tune_spec_k(
    config: Any = None,
    *,
    draft_layers: int = 1,
    dtype: Any = jnp.float32,
    db: TuningDB | None = None,
    candidates: tuple[int, ...] | None = None,
    num_requests: int = 6,
    prompt_len: int = 8,
    max_new_tokens: int = 16,
    seed: int = 0,
) -> dict[str, Any]:
    """Search the speculative proposal depth for one target/draft pair.

    Analytic models of speculative decoding need the acceptance rate —
    which is a property of the two REAL models on REAL token streams, not
    something to assume. So this tuner measures end to end: for each
    candidate ``k`` (0 = plain decode, always in the field) it builds a
    serving engine with the self-draft (the target's first
    ``draft_layers`` layers via ``truncate_lm_params``), replays the same
    deterministic request set, and scores emitted tokens per wall-second.
    The per-``k`` measured acceptance rate rides along in the candidate
    record, and the winner (with its acceptance rate) is persisted under
    :func:`spec_k_key`. Greedy parity makes every candidate emit
    identical streams, so this is a pure throughput race.
    """
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.models.transformer import (
        draft_config,
        truncate_lm_params,
    )
    from deeplearning_mpi_tpu.serving import EngineConfig, ServingEngine
    from deeplearning_mpi_tpu.telemetry import MetricsRegistry

    cfg = config or TransformerConfig.tiny()
    model = TransformerLM(config=cfg, dtype=dtype)
    params = model.init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    d_cfg = draft_config(cfg, draft_layers)
    d_params = truncate_lm_params(params, draft_layers)
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=prompt_len).tolist()
        for _ in range(num_requests)
    ]
    max_k = max(candidates or SPEC_K_CANDIDATES)
    base = EngineConfig(
        max_slots=max(num_requests // 2, 1), block_size=8,
        num_blocks=4 * num_requests * ((prompt_len + max_new_tokens) // 8 + 2),
        max_blocks_per_seq=(prompt_len + max_new_tokens + max_k) // 8 + 2,
        prefill_chunk=8,
    )

    results: list[dict[str, Any]] = []
    best: dict[str, Any] | None = None
    for k in sorted(set(candidates or SPEC_K_CANDIDATES)):
        registry = MetricsRegistry()
        engine = ServingEngine(
            cfg, params,
            dataclasses.replace(base, spec_k=k),
            dtype=dtype, registry=registry,
            draft_config=d_cfg if k else None,
            draft_params=d_params if k else None,
        )
        for p in prompts:
            engine.submit(p, max_new_tokens)
        # Absorb compiles outside the timed window: one step compiles
        # prefill, and the requests finish over the remaining steps.
        engine.step()
        t0 = time.perf_counter()
        finished = engine.run_until_idle()
        wall = time.perf_counter() - t0
        tokens = sum(len(r.generated) for r in finished)
        snap = registry.snapshot()
        proposed = snap.get("spec_proposed_total", 0)
        accepted = snap.get("spec_accepted_total", 0)
        entry = {
            "spec_k": int(k),
            "tokens_per_s": tokens / wall if wall > 0 else 0.0,
            "seconds": wall,
            "accept_rate": accepted / proposed if proposed else None,
        }
        results.append(entry)
        if best is None or entry["tokens_per_s"] > best["tokens_per_s"]:
            best = entry
    params_out = {
        "spec_k": best["spec_k"], "accept_rate": best["accept_rate"],
    }
    if db is not None:
        db.record_key(
            spec_k_key(cfg, draft_layers, dtype), params_out,
            best_seconds=best["seconds"], candidates=results,
            kernel="spec_k", draft_layers=int(draft_layers),
            dtype=jnp.dtype(dtype).name, backend=jax.default_backend(),
        )
    return params_out


# -- whole-step schedule ------------------------------------------------------

#: Remat policies the step tuner tries, cheapest-memory last
#: (``models.transformer.TransformerLM.remat``).
STEP_REMAT_CANDIDATES = ("none", "dots", "full")


def step_candidates(
    dp: int, *, grad_accums: tuple[int, ...] = (1, 2)
) -> list[dict[str, Any]]:
    """Default whole-step search space: remat policy × grad-accum chunking
    × {GSPMD, overlapped} schedule. Donation stays on (the runtime vetoes
    it where unsafe); overlap candidates only exist with real data
    parallelism."""
    overlaps = (False, True) if dp > 1 else (False,)
    return [
        {"remat": remat, "grad_accum": ga, "donate": True, "overlap": ov}
        for remat in STEP_REMAT_CANDIDATES
        for ga in grad_accums
        for ov in overlaps
    ]


def tune_step_schedule(
    model: str = "lm",
    *,
    batch_size: int = 8,
    seq_len: int = 16,
    config: Any = None,
    mesh: Any = None,
    dtype: Any = jnp.float32,
    db: TuningDB | None = None,
    candidates: list[dict[str, Any]] | None = None,
    steps: int = 5,
    repeats: int = 2,
    rtol: float = 1e-5,
) -> dict[str, Any]:
    """Search the whole-train-step schedule space for one (model, shape,
    mesh, dtype) and persist the winner under its ``step|...`` key.

    Oracle-first, like the kernel tuners: the UNTUNED step (no remat,
    ``grad_accum=1``, GSPMD schedule, no donation) is run first and its
    per-step loss trajectory recorded; every candidate must reproduce that
    trajectory (within ``rtol`` — grad-accum chunking only reassociates
    float sums) over the same ``steps`` batches *before* it may be timed.
    A schedule that changes the training math is rejected
    (``rejected: "numerics"``), not preferred — the DB makes steps faster,
    never different.

    Candidates the configuration cannot run (overlap on dp=1, a batch the
    grad-accum factor doesn't divide, ``OverlapUnsupported``) are recorded
    as ``rejected: "unsupported"`` and skipped. Currently LM-only — the
    ``step`` key space is per-model-family, so extending to the vision
    tasks is a new candidate builder, not a schema change.
    """
    import numpy as np

    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
    from deeplearning_mpi_tpu.parallel import shard_state
    from deeplearning_mpi_tpu.parallel.tensor_parallel import (
        infer_state_sharding,
    )
    from deeplearning_mpi_tpu.parallel.zero import (
        OverlapUnsupported,
        make_overlapped_train_step,
    )
    from deeplearning_mpi_tpu.runtime.mesh import (
        MeshSpec,
        batch_sharding,
        create_mesh,
    )
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer

    if model != "lm":
        raise ValueError(
            f"step tuning currently covers the 'lm' task only, got {model!r}"
        )
    if mesh is None:
        mesh = create_mesh(MeshSpec(data=len(jax.devices())))
    dp = int(mesh.shape.get("data", 1))
    zero = dp > 1
    cfg = config or TransformerConfig(
        vocab_size=256, num_layers=1, num_heads=2, head_dim=32,
        d_model=64, d_ff=256, onehot_embed=True,
    )

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(steps):
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch_size, seq_len)), jnp.int32
        )
        mask = jnp.asarray(
            rng.integers(0, 2, (batch_size, seq_len)), jnp.float32
        )
        batches.append({
            "tokens": jax.device_put(tokens, batch_sharding(mesh, ndim=2)),
            "mask": jax.device_put(mask, batch_sharding(mesh, ndim=2)),
        })

    def build_state(remat: Any):
        mdl = TransformerLM(config=cfg, dtype=dtype, remat=remat)
        st = create_train_state(
            mdl, jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            build_optimizer("adam", 1e-2),
        )
        return shard_state(st, mesh, zero=zero)

    def build_step(cand: dict[str, Any], state: Any):
        if cand.get("overlap"):
            return make_overlapped_train_step(
                model, state, mesh,
                donate=cand.get("donate", True),
                grad_accum=cand.get("grad_accum", 1),
            )
        shardings = (
            infer_state_sharding(state, mesh, zero=zero) if zero else None
        )
        return make_train_step(
            model, donate=cand.get("donate", True),
            grad_accum=cand.get("grad_accum", 1),
            state_shardings=shardings, mesh=mesh,
        )

    def run(cand: dict[str, Any]) -> list[float]:
        state = build_state(cand.get("remat", "none"))
        step = build_step(cand, state)
        losses = []
        for b in batches:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
        return losses

    oracle_cand = {
        "remat": "none", "grad_accum": 1, "donate": False, "overlap": False,
    }
    oracle = run(oracle_cand)

    results: list[dict[str, Any]] = []
    best: dict[str, Any] | None = None
    for cand in candidates if candidates is not None else step_candidates(dp):
        entry = dict(cand)
        ga = cand.get("grad_accum", 1)
        local_batch = batch_size // dp if cand.get("overlap") else batch_size
        if local_batch % ga:
            entry["rejected"] = "unsupported"
            results.append(entry)
            continue
        try:
            losses = run(cand)
        except OverlapUnsupported:
            entry["rejected"] = "unsupported"
            results.append(entry)
            continue
        if not np.allclose(losses, oracle, rtol=rtol, atol=1e-7):
            entry["rejected"] = "numerics"
            results.append(entry)
            continue
        # Timing: whole verified N-step loop, fresh state per repeat so
        # donation candidates never re-consume a donated buffer.
        times = []
        for _ in range(repeats):
            state = build_state(cand.get("remat", "none"))
            step = build_step(cand, state)
            state, _ = step(state, batches[0])  # absorb compile
            jax.block_until_ready(state.params)
            t0 = time.perf_counter()
            for b in batches:
                state, _ = step(state, b)
            jax.block_until_ready(state.params)
            times.append((time.perf_counter() - t0) / steps)
        entry["seconds"] = statistics.median(times)
        results.append(entry)
        if best is None or entry["seconds"] < best["seconds"]:
            best = entry
    if best is None:
        return {}
    params = {
        k: best[k] for k in ("remat", "grad_accum", "donate", "overlap")
    }
    if db is not None:
        db.record_key(
            step_tuning_key(model, (batch_size, seq_len), mesh, dtype),
            params,
            best_seconds=best["seconds"],
            candidates=results,
            kernel="step",
            model=model,
            shape=[int(batch_size), int(seq_len)],
            mesh=_mesh_desc(mesh),
            dtype=jnp.dtype(dtype).name,
            backend=jax.default_backend(),
        )
    return params


def tuned_step_schedule(
    model: str,
    shape: tuple[int, ...],
    mesh: Any,
    dtype: Any = jnp.float32,
    *,
    db: TuningDB | None = None,
) -> dict[str, Any] | None:
    """The tuned whole-step schedule for this exact (model, shape, mesh,
    dtype), or None when untuned — never raises, like every call-site
    consult: a missing/corrupt/poisoned DB means 'use the defaults', not a
    failed training run."""
    try:
        db = db if db is not None else default_db()
        if db is None:
            return None
        return db.lookup_key(
            step_tuning_key(model, tuple(shape), mesh, dtype)
        )
    except Exception:
        return None
