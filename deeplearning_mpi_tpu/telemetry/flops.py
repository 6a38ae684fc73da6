"""Analytic FLOPs estimates and MFU — the scaling literature's headline metric.

MFU (model FLOPs utilization) divides the *useful* model FLOPs by what the
hardware could have done in the same wall time:

    mfu = flops_per_step / (step_seconds * n_devices * peak_flops_per_device)

"Useful" means the analytic cost of the model's math — matmuls and convs —
NOT what XLA executed (rematerialization, padding, and masked positions all
burn hardware FLOPs that don't count). That convention is what makes MFU
comparable across frameworks and papers (PaLM's appendix B formulation).

Training cost uses the standard factor-3 rule: backward ≈ 2× forward
(one matmul per input gradient, one per weight gradient), so
``train = 3 × forward``. Attention scores/values matmuls are counted at
the causally-visible positions (S/2 average, windowed where applicable) —
the kernels here (`ops/pallas/flash_attention.py` trimmed grids,
`parallel/ring_attention.py` rotation skipping) genuinely skip the dead
half, so counting full S² would overstate MFU on exactly the paths this
repo optimized.

Peak FLOPs per device come from one table of TPU generations (bf16 peak,
the training dtype), reached through the ``device_kind`` strings the runtime
actually reports (:data:`TPU_KINDS`), with a ``DMT_PEAK_FLOPS`` env override.
A TPU whose kind is not in the table is an error, not a guessed generation.
On CPU there is no meaningful peak; a nominal constant keeps MFU *defined*
(the report needs a non-null column and relative comparisons across runs on
the same host are still valid) and the override makes it honest if anyone
calibrates their machine.
"""

from __future__ import annotations

import os
from typing import Any

import jax

#: bf16 peak FLOPs/s per chip by TPU generation (public spec sheets).
PEAK_FLOPS: dict[str, float] = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

#: ``device_kind`` (lower-cased) as the runtime reports it → generation key
#: of the tables here. The strings are the ones jax itself switches on
#: (``jax/_src/pallas/mosaic/tpu_info.py``): a v5e reports "TPU v5 lite",
#: not "v5e".
TPU_KINDS: dict[str, str] = {
    "tpu v2": "v2",
    "tpu v3": "v3",
    "tpu v4": "v4",
    "tpu v5 lite": "v5e",
    "tpu v5e": "v5e",
    "tpu v5": "v5p",
    "tpu v5p": "v5p",
    "tpu v6 lite": "v6e",
    "tpu v6e": "v6e",
}

#: Nominal CPU "peak" — a few AVX cores' worth. Arbitrary but stable, so
#: CPU-mesh MFU is non-null and comparable run-to-run on one host.
CPU_NOMINAL_PEAK_FLOPS = 200e9

#: Aggregate ICI bandwidth per chip in bytes/s (public per-chip interconnect
#: specs, bits/8). Nominal: real achievable bandwidth depends on topology and
#: collective — these set the scale for the overlap estimate, and
#: ``DMT_LINK_BANDWIDTH`` overrides with a calibrated number.
LINK_BANDWIDTH: dict[str, float] = {
    "v2": 62e9,
    "v3": 82e9,
    "v4": 300e9,
    "v5e": 200e9,
    "v5p": 600e9,
    "v6e": 448e9,
}

#: Nominal CPU "interconnect" (shared-memory transfers between virtual
#: devices) — same convention as CPU_NOMINAL_PEAK_FLOPS: stable, not real.
CPU_NOMINAL_LINK_BANDWIDTH = 10e9


def _tpu_generation(device: Any) -> str | None:
    """Generation key for a TPU device, ``None`` for any other platform;
    raises on a TPU kind the tables do not know."""
    if getattr(device, "platform", "") != "tpu":
        return None
    kind = getattr(device, "device_kind", "")
    try:
        return TPU_KINDS[kind.lower()]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {kind!r}: add it to "
            "telemetry.flops.TPU_KINDS with its spec-sheet peaks (or set "
            "DMT_PEAK_FLOPS / DMT_LINK_BANDWIDTH)"
        ) from None


def device_peak_flops(device: Any | None = None) -> float:
    """Peak FLOPs/s for ``device`` (default: first local device).

    Resolution order: ``DMT_PEAK_FLOPS`` env var (calibrated override) →
    TPU generation table via ``device_kind`` (unknown kind raises) → CPU
    nominal constant.
    """
    env = os.environ.get("DMT_PEAK_FLOPS")
    if env:
        return float(env)
    gen = _tpu_generation(device if device is not None else jax.devices()[0])
    return PEAK_FLOPS[gen] if gen else CPU_NOMINAL_PEAK_FLOPS


def device_link_bandwidth(device: Any | None = None) -> float:
    """Nominal interconnect bytes/s for ``device`` (default: first local).

    Resolution order mirrors :func:`device_peak_flops`:
    ``DMT_LINK_BANDWIDTH`` env var → TPU generation table → CPU nominal.
    """
    env = os.environ.get("DMT_LINK_BANDWIDTH")
    if env:
        return float(env)
    gen = _tpu_generation(device if device is not None else jax.devices()[0])
    return LINK_BANDWIDTH[gen] if gen else CPU_NOMINAL_LINK_BANDWIDTH


def overlap_fraction(
    comm_bytes_per_step: float,
    issued_flops_per_step: float,
    *,
    n_devices: int | None = None,
    peak_flops_per_device: float | None = None,
    link_bandwidth_per_device: float | None = None,
) -> float | None:
    """Estimated fraction of per-step collective time hideable under compute.

    Roofline-style: compute time ≈ issued FLOPs / (n · peak), collective
    time ≈ per-device wire bytes / link bandwidth. When compute covers the
    comms entirely the scheduler *can* hide them (fraction 1.0 — whether it
    *does* is what ``mfu_gap`` and the profiler answer); when comms exceed
    compute, at most compute/comm of them can hide and the step is
    communication-bound. None on degenerate inputs; 1.0 when there are no
    collective bytes to hide.
    """
    if not issued_flops_per_step or issued_flops_per_step <= 0:
        return None
    if comm_bytes_per_step is None or comm_bytes_per_step < 0:
        return None
    if not comm_bytes_per_step:
        return 1.0
    if n_devices is None:
        n_devices = jax.device_count()
    if peak_flops_per_device is None:
        peak_flops_per_device = device_peak_flops()
    if link_bandwidth_per_device is None:
        link_bandwidth_per_device = device_link_bandwidth()
    compute_s = issued_flops_per_step / (n_devices * peak_flops_per_device)
    comm_s = (comm_bytes_per_step / n_devices) / link_bandwidth_per_device
    if comm_s <= 0:
        return 1.0
    return min(1.0, compute_s / comm_s)


def xla_cost_analysis(compiled: Any) -> dict[str, float]:
    """FLOPs / bytes the compiled executable will actually execute, from
    XLA's own cost analysis — the *measured* complement to the analytic
    estimators below (which count only the model's useful math and are what
    MFU is defined over; XLA's number additionally includes remat, padding,
    and masked work, so comparing the two bounds the overhead).

    Accepts a ``jax.stages.Compiled`` (``compiler/aot.py`` passes one per
    warmed program); the dict is keyed ``'flops'`` / ``'bytes accessed'``.
    Returns ``{}`` where the backend exposes nothing (keys absent, never
    faked — same convention as ``hbm_usage``).
    """
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if not isinstance(ca, dict):
        return {}
    out: dict[str, float] = {}
    flops = ca.get("flops")
    if isinstance(flops, (int, float)) and flops > 0:
        out["flops"] = float(flops)
    nbytes = ca.get("bytes accessed")
    if isinstance(nbytes, (int, float)) and nbytes > 0:
        out["bytes_accessed"] = float(nbytes)
    return out


def mfu(
    flops_per_step: float,
    step_seconds: float,
    *,
    n_devices: int | None = None,
    peak_flops_per_device: float | None = None,
) -> float | None:
    """Model FLOPs utilization in [0, ~1]; None when inputs are degenerate."""
    if not flops_per_step or not step_seconds or step_seconds <= 0:
        return None
    if n_devices is None:
        n_devices = jax.device_count()
    if peak_flops_per_device is None:
        peak_flops_per_device = device_peak_flops()
    return flops_per_step / (step_seconds * n_devices * peak_flops_per_device)


def mfu_gap_attribution(
    phase_seconds: dict[str, float],
    duration_s: float,
    *,
    mfu_issued: float | None,
    mfu_gap: float | None,
) -> dict[str, float]:
    """Decompose ``mfu_gap`` into the trainer's measured step phases.

    ``mfu_gap = mfu_issued - mfu`` is the utilization lost to everything
    that isn't useful model math. With per-phase wall-clock attribution
    (``train/trainer.py`` tracing: data_wait / h2d / collective_tail / …),
    each non-compute phase's share of the epoch directly forfeits that
    fraction of the *achievable* utilization:

        mfu_gap_<phase> = mfu_issued · (phase_seconds / duration)

    The remainder — remat recompute, padding, kernel inefficiency, and any
    stall the fences didn't isolate — lands in ``mfu_gap_residual`` so the
    returned values sum to ``mfu_gap`` exactly (the report can render the
    decomposition as shares of a closed total). The ``compute`` phase is
    the useful-work bucket and never charged to the gap.

    Returns ``{}`` on degenerate inputs (no duration, or the run didn't
    compute MFU at all) — keys absent, never faked.
    """
    if not duration_s or duration_s <= 0:
        return {}
    if mfu_issued is None or mfu_gap is None:
        return {}
    out: dict[str, float] = {}
    explained = 0.0
    for name, secs in phase_seconds.items():
        if name == "compute":
            continue
        share = mfu_issued * (float(secs) / duration_s)
        out[f"mfu_gap_{name}"] = share
        explained += share
    out["mfu_gap_residual"] = mfu_gap - explained
    return out


# ---------------------------------------------------------------------------
# Transformer / MoE (models/transformer.py, models/moe.py)
# ---------------------------------------------------------------------------

def transformer_fwd_flops(config: Any, batch: int, seq_len: int) -> float:
    """Forward FLOPs for one ``TransformerLM`` batch.

    Counts matmuls only (norms/activations/RoPE are O(d) noise):

    - embedding lookup is a gather (0 FLOPs); the LM head is a matmul,
      2·d·V per token (tied or not, the matmul runs);
    - per block: q/k/v/out projections (GQA-aware: k/v project to
      ``num_kv_heads·head_dim``), attention scores+values at
      2 · 2 · S_visible · H · Dh per token with S_visible the average
      causally-visible positions (S/2, capped by the sliding window), and
      SwiGLU MLP — three matmuls (gate, up, down), 6·d·ff per token;
    - MoE blocks swap the dense MLP for router (2·d·E) + top_k experts'
      worth of SwiGLU (GShard counts only ACTIVE expert FLOPs).
    """
    d = config.d_model
    h = config.num_heads
    hkv = getattr(config, "num_kv_heads", None) or h
    dh = config.head_dim
    ff = config.d_ff
    layers = config.num_layers
    vocab = config.vocab_size
    tokens = batch * seq_len

    # A layer's own window where the model describes its layers one by one
    # (TransformerConfig.layers), else the global one; the mean over layers.
    windows = [spec.window for spec in getattr(config, "layers", ())] or [
        getattr(config, "attention_window", 0)
    ]
    # 0/None = full causal attention, no cap
    s_visible = sum(
        min(seq_len / 2.0, float(w)) if w else seq_len / 2.0 for w in windows
    ) / len(windows)

    per_token_block = 0.0
    # Projections: q (d→H·Dh), k+v (d→Hkv·Dh each), out (H·Dh→d).
    per_token_block += 2 * d * (h * dh) * 2       # q + out
    per_token_block += 2 * d * (hkv * dh) * 2     # k + v
    # Attention: scores (2·S_vis·H·Dh) + values (2·S_vis·H·Dh) per token.
    per_token_block += 4 * s_visible * h * dh

    experts = getattr(config, "moe_experts", None) or 0
    if experts:
        top_k = getattr(config, "moe_top_k", 1) or 1
        per_token_block += 2 * d * experts        # router logits
        per_token_block += top_k * 6 * d * ff     # active experts' SwiGLU
    else:
        per_token_block += 6 * d * ff             # gate + up + down

    head = 2 * d * vocab  # LM head matmul per token
    return tokens * (layers * per_token_block + head)


def transformer_train_flops(config: Any, batch: int, seq_len: int) -> float:
    return 3.0 * transformer_fwd_flops(config, batch, seq_len)


def transformer_remat_flops(
    config: Any, batch: int, seq_len: int, *, remat: Any = "none"
) -> float:
    """Extra matmul FLOPs one train step RECOMPUTES under rematerialization.

    These are issued by the hardware but are not model FLOPs — MFU's
    definition excludes them, so they belong on the issued side of the
    ledger (:func:`transformer_issued_flops`), where ``mfu_gap`` makes the
    overhead visible instead of silently inflating utilization.

    Policies (``TransformerLM.remat``):

    - ``"none"``/``False``: nothing recomputed — 0.
    - ``"dots"`` (``jax.checkpoint_policies.checkpoint_dots``): matmul
      *outputs* are saved; only the elementwise glue between them is
      recomputed, which this module counts as O(d) noise everywhere — 0
      extra matmul FLOPs, at ~the activation memory of the dots.
    - ``"full"``/``True``: every block's forward is re-executed inside the
      backward pass — one extra forward's worth of block FLOPs. The LM head
      is outside the remat boundary (``nn.remat`` wraps ``Block``) and is
      not recomputed.
    """
    if isinstance(remat, str):
        remat = remat.lower()
    if remat in ("none", "", None, False):
        return 0.0
    if remat == "dots":
        return 0.0
    if remat in ("full", True):
        head = 2.0 * config.d_model * config.vocab_size * batch * seq_len
        return transformer_fwd_flops(config, batch, seq_len) - head
    raise ValueError(f"unknown remat policy {remat!r}")


def transformer_issued_flops(
    config: Any, batch: int, seq_len: int, *, remat: Any = "none"
) -> float:
    """FLOPs the hardware issues per train step: model train FLOPs plus
    remat recompute. Feed this to ``Trainer(issued_flops_per_step=...)`` /
    ``mfu`` to get ``mfu_issued``; the difference from plain ``mfu`` is the
    remat tax."""
    return transformer_train_flops(config, batch, seq_len) + (
        transformer_remat_flops(config, batch, seq_len, remat=remat)
    )


# ---------------------------------------------------------------------------
# ResNet (models/resnet.py)
# ---------------------------------------------------------------------------

_RESNET_STAGES = {
    "resnet18": ((2, 2, 2, 2), False),
    "resnet34": ((3, 4, 6, 3), False),
    "resnet50": ((3, 4, 6, 3), True),
    "resnet101": ((3, 4, 23, 3), True),
    "resnet152": ((3, 8, 36, 3), True),
}


def _conv_flops(k: int, cin: int, cout: int, oh: float, ow: float) -> float:
    return 2.0 * k * k * cin * cout * oh * ow


def resnet_fwd_flops(
    arch: str,
    batch: int,
    image_size: int = 32,
    *,
    num_classes: int = 10,
    stem: str = "cifar",
) -> float:
    """Forward FLOPs for one ResNet batch (models/resnet.py topology).

    Walks the stages exactly as the model builds them: stem, then four
    stages of Basic (2×3×3) or Bottleneck (1×1 → 3×3 → 1×1·4) blocks with
    stride 2 at each stage boundary after the first, projection shortcut
    where shape changes, then the Dense head.
    """
    stages, bottleneck = _RESNET_STAGES[arch]
    s = float(image_size)
    flops = 0.0
    cin = 3
    if stem == "imagenet":
        s /= 2  # 7×7 stride-2 stem
        flops += _conv_flops(7, cin, 64, s, s)
        s /= 2  # 3×3 stride-2 maxpool
    else:
        flops += _conv_flops(3, cin, 64, s, s)
    cin = 64
    for stage_idx, num_blocks in enumerate(stages):
        width = 64 * (2 ** stage_idx)
        for block_idx in range(num_blocks):
            stride = 2 if (stage_idx > 0 and block_idx == 0) else 1
            s_out = s / stride
            if bottleneck:
                cout = width * 4
                flops += _conv_flops(1, cin, width, s_out, s_out)
                flops += _conv_flops(3, width, width, s_out, s_out)
                flops += _conv_flops(1, width, cout, s_out, s_out)
            else:
                cout = width
                flops += _conv_flops(3, cin, width, s_out, s_out)
                flops += _conv_flops(3, width, cout, s_out, s_out)
            if stride != 1 or cin != cout:
                flops += _conv_flops(1, cin, cout, s_out, s_out)  # projection
            cin, s = cout, s_out
    flops += 2.0 * cin * num_classes  # head
    return batch * flops


def resnet_train_flops(arch: str, batch: int, image_size: int = 32, **kw: Any) -> float:
    return 3.0 * resnet_fwd_flops(arch, batch, image_size, **kw)


# ---------------------------------------------------------------------------
# UNet (models/unet.py)
# ---------------------------------------------------------------------------

def unet_fwd_flops(
    batch: int,
    image_size: int,
    *,
    features: tuple[int, ...] = (64, 128, 256, 512),
    in_channels: int = 1,
    out_channels: int = 2,
    dim: int = 2,
) -> float:
    """Forward FLOPs for one UNet batch (models/unet.py topology).

    Encoder: DoubleConv (2 × conv3^dim) per level + 2× downsample;
    bottleneck DoubleConv at 2·features[-1]; decoder: ConvTranspose
    (2^dim kernel, stride 2, halving channels) then DoubleConv on the
    skip-concatenated input; 1×1 head. ``dim`` generalizes to 3-D (voxel
    counts scale as size^dim, conv kernels as 3^dim).
    """
    def conv(k_vol: float, cin: int, cout: int, vox: float) -> float:
        return 2.0 * k_vol * cin * cout * vox

    k3 = 3.0 ** dim
    kt = 2.0 ** dim
    size = float(image_size)
    vox = size ** dim
    flops = 0.0
    cin = in_channels
    enc_vox = []
    for f in features:
        flops += conv(k3, cin, f, vox) + conv(k3, f, f, vox)
        enc_vox.append(vox)
        cin = f
        size /= 2
        vox = size ** dim
    bott = features[-1] * 2
    flops += conv(k3, cin, bott, vox) + conv(k3, bott, bott, vox)
    cin = bott
    for f, up_vox in zip(reversed(features), reversed(enc_vox)):
        flops += conv(kt, cin, f, up_vox)                 # transposed conv
        flops += conv(k3, 2 * f, f, up_vox) + conv(k3, f, f, up_vox)
        cin = f
    flops += conv(1.0, cin, out_channels, enc_vox[0])     # 1×1 head
    return batch * flops


def unet_train_flops(batch: int, image_size: int, **kw: Any) -> float:
    return 3.0 * unet_fwd_flops(batch, image_size, **kw)
