"""Loss functions.

Parity targets: ``nn.CrossEntropyLoss()`` for the ResNet trainer
(``pytorch/resnet/main.py:113``) and ``nn.BCEWithLogitsLoss()`` for the UNet
trainer (``pytorch/unet/train.py:160-162``). Both are mean-reduced over all
elements, matching the torch defaults. All losses are computed in float32
regardless of input dtype — on TPU the model runs bfloat16 through the MXU but
loss/softmax reductions need f32 accumulation for stability. The float32
view of the logits lives inside the fusions that reduce it: the token
cross-entropy (:func:`_token_nll`) reads the logits where the head wrote
them, once forward and once backward, and stores nothing of their size.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def _is_label(shape: tuple[int, ...], labels: jax.Array) -> jax.Array:
    """One-hot of ``labels`` over the last axis of ``shape``, as a comparison
    with an iota: XLA fuses it into whichever pass over the logits uses it,
    where a gather's transpose is a scatter into a zero-filled buffer."""
    vocab = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return vocab == labels[..., None]


def _nll_and_lse(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    label_logit = jnp.sum(jnp.where(_is_label(x.shape, labels), x, 0.0), axis=-1)
    return lse - label_logit, lse


@jax.custom_vjp
def _token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-element negative log-likelihood over the last axis:
    ``logsumexp(x) - x[label]`` with ``x = float32(logits)``.

    The logits are read once forward (row maximum, exp-sum and the label's
    logit in one pass, every reduction in float32) and once backward; no
    log-prob tensor is written and nothing of the logits' size is saved
    beside the logits themselves, in the dtype the head wrote them. The
    backward is one elementwise pass, ``g * (exp(x - lse) - onehot)``, cast to
    the logits' dtype where it is produced and written once; a row whose
    cotangent is 0 gets an exactly zero gradient, whatever its logits hold.
    """
    return _nll_and_lse(logits, labels)[0]


def _token_nll_fwd(logits, labels):
    nll, lse = _nll_and_lse(logits, labels)
    return nll, (logits, labels, lse)


def _token_nll_bwd(residuals, g):
    logits, labels, lse = residuals
    x = logits.astype(jnp.float32)
    g = g[..., None]
    softmax = jnp.exp(x - lse[..., None])
    grad = g * (softmax - _is_label(x.shape, labels).astype(jnp.float32))
    grad = jnp.where(g != 0, grad, 0.0).astype(logits.dtype)
    # Written once, here. Left to itself the TPU compiler recomputes this pass
    # inside the operand of every matmul that reads the gradient: the LM
    # head's two gradient matmuls then run 2.4 and 1.4 ms longer a step, three
    # times what the one write and two reads cost (PERF.md §6, PR 33).
    return jax.lax.optimization_barrier(grad), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def masked_mean(values: jax.Array, where: jax.Array | None) -> jax.Array:
    """Mean of ``values``, optionally weighted by a broadcast-compatible
    validity mask (0 = padded element, excluded) — [B] per-example masks and
    [B, T] per-token masks both work."""
    if where is None:
        return jnp.mean(values)
    w = where.astype(jnp.float32)
    return jnp.sum(values * w) / jnp.maximum(jnp.sum(w), 1.0)


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, where: jax.Array | None = None
) -> jax.Array:
    """Mean softmax cross-entropy with integer labels.

    Equivalent of ``nn.CrossEntropyLoss()(outputs, labels)``
    (``pytorch/resnet/main.py:113,129``): softmax over the last axis, mean
    over the batch. ``where`` ([B], 1 = real example) excludes wrap-padded
    eval rows.
    """
    return masked_mean(_token_nll(logits, labels), where)


def bce_per_image(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-image mean binary cross-entropy on logits, shape [B].

    The pre-reduction form of :func:`sigmoid_binary_cross_entropy`; exposed so
    data-parallel schedules that need the batch mean in explicit
    sum-over-shards form (``parallel.zero``'s overlapped step) share these
    exact per-image values with the GSPMD loss path.
    """
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    per_elem = (
        jnp.maximum(logits, 0.0)
        - logits * targets
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
    return jnp.mean(per_elem, axis=tuple(range(1, per_elem.ndim)))


def sigmoid_binary_cross_entropy(
    logits: jax.Array, targets: jax.Array, where: jax.Array | None = None
) -> jax.Array:
    """Mean binary cross-entropy on logits.

    Equivalent of ``nn.BCEWithLogitsLoss()(predictions, masks)``
    (``pytorch/unet/train.py:160-162,183``): elementwise
    ``max(x,0) - x*y + log(1+exp(-|x|))``, mean over all elements — the same
    log-sum-exp-stable form torch uses. ``where`` ([B], 1 = real example)
    excludes wrap-padded eval rows (equal-sized images ⇒ the all-elements
    mean equals the mean of per-image means).
    """
    return masked_mean(bce_per_image(logits, targets), where)


def dice_per_image(
    logits: jax.Array, targets: jax.Array, *, eps: float = 1e-8
) -> jax.Array:
    """Per-image soft Dice loss (1 - soft Dice coefficient), shape [B].

    The pre-reduction form of :func:`dice_loss`, exposed for the same reason
    as :func:`bce_per_image` — Dice is per-image before the batch mean, so
    the data-parallel sum-over-shards form needs exactly these values.
    """
    probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    targets = targets.astype(jnp.float32)
    reduce_axes = tuple(range(1, logits.ndim))
    intersection = jnp.sum(probs * targets, axis=reduce_axes)
    union = jnp.sum(probs, axis=reduce_axes) + jnp.sum(targets, axis=reduce_axes)
    dice = (2.0 * intersection + eps) / (union + eps)
    return 1.0 - dice


def dice_loss(
    logits: jax.Array,
    targets: jax.Array,
    where: jax.Array | None = None,
    *,
    eps: float = 1e-8,
) -> jax.Array:
    """Soft Dice loss (1 - soft Dice coefficient), averaged over the batch.

    The reference only uses Dice as an eval metric
    (``pytorch/unet/train.py:124-140``); offering it as a training loss is a
    standard segmentation extension (``dmt-train-unet --loss dice``). Uses
    the same ``eps`` smoothing as the reference's metric. ``where`` ([B],
    1 = real example) excludes wrap-padded eval rows, like the other losses.
    """
    return masked_mean(dice_per_image(logits, targets, eps=eps), where)


def _next_token_targets(
    tokens: jax.Array, mask: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """``(labels, weights)``, both [B, S]: position ``i`` predicts
    ``tokens[:, i + 1]`` with weight ``mask[:, i + 1]`` (1 without a mask);
    the last position predicts nothing and weighs 0.

    The labels move, not the logits: ``logits[:, :-1]`` has S - 1 rows, which
    do not tile, so XLA copies the slice forward and pads its gradient back.
    """
    live = jnp.ones(tokens.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
    shift = lambda a: jnp.pad(a[:, 1:], ((0, 0), (0, 1)))  # noqa: E731 — zeros at the end
    return shift(tokens), shift(live)


def _live_nll(logits: jax.Array, labels: jax.Array, weights: jax.Array) -> jax.Array:
    """Per-position NLL, exactly 0 where the weight is 0: such a position's
    logits (the last position's, a padded one's) reach neither the sum nor,
    through :func:`_token_nll`'s backward, any gradient — non-finite or not."""
    return jnp.where(weights > 0, _token_nll(logits, labels), 0.0)


def lm_token_nll(
    logits: jax.Array, tokens: jax.Array, mask: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """``(nll, weights)``, both [B, S] float32, of the next-token objective;
    the loss is their :func:`masked_mean`.

    The one place that aligns logits with labels: :func:`lm_cross_entropy`
    and ``parallel.zero``'s sum-over-shards form of the same mean both reduce
    these two arrays, so the two schedules cannot drift apart.
    """
    labels, weights = _next_token_targets(tokens, mask)
    return _live_nll(logits, labels, weights), weights


def lm_cross_entropy(
    logits: jax.Array, tokens: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Next-token LM loss: position ``i`` of ``logits`` predicts
    ``tokens[:, i + 1]``; the mean NLL over the S - 1 predicting positions.

    No reference analog (the reference has no sequence models — SURVEY.md
    §5.7); this is the training loss for the transformer workload. ``mask``
    (1 = real token) excludes padding from the mean; an all-zero mask gives 0.
    All S rows of the logits are read where they lie, once forward and once
    backward (:func:`_token_nll`); the last row weighs 0, so it gets an
    exactly zero gradient and a non-finite logit there changes nothing.
    """
    return masked_mean(*lm_token_nll(logits, tokens, mask))


def chunked_lm_loss(
    x: jax.Array,
    head_kernel: jax.Array,
    tokens: jax.Array,
    *,
    chunk_size: int,
    mask: jax.Array | None = None,
    compute_dtype: Any = None,
) -> jax.Array:
    """Next-token loss from pre-head activations, never materializing the
    full logits.

    ``lm_cross_entropy(x @ head_kernel, tokens)`` keeps the ``[B, S, V]``
    logits resident from the forward to the backward pass and writes a
    gradient of the same size in the head's dtype — at 32k tokens over a 32k
    vocab ~2.1 GB each in bfloat16, the two biggest tensors in the
    long-context step. Here the head matmul and the cross-entropy run
    chunk-by-chunk over the sequence inside a ``lax.scan``, with each chunk
    under ``jax.checkpoint`` so the backward recomputes its ``[B, chunk, V]``
    logits tile instead of saving it: peak logits memory drops from O(S·V) to
    O(chunk·V) in both passes for one extra head matmul per chunk in the
    backward.

    Args: ``x`` — final-norm output ``[B, S, d]`` (any dtype);
    ``head_kernel`` — ``[d, V]`` (tied embeddings: ``embedding.T``);
    ``tokens`` — ``[B, S]`` int; ``mask`` (1 = real token) as in
    :func:`lm_cross_entropy`; ``compute_dtype`` — matmul dtype (default:
    ``x.dtype``, matching the model's head). Numerics: the same labels,
    weights and per-position NLL as the dense path (all S positions are
    chunked, the last one weighing 0), float32 reductions.
    """
    compute_dtype = compute_dtype or x.dtype
    batch, seq, _ = x.shape
    x_in = x.astype(compute_dtype)
    labels, weights = _next_token_targets(tokens, mask)
    chunk_size = max(1, min(chunk_size, seq))
    pad = (-seq) % chunk_size
    if pad:
        x_in = jnp.pad(x_in, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))  # zero weight = excluded
    n_chunks = (seq + pad) // chunk_size
    split = lambda a: a.reshape(  # noqa: E731 — [B, S(+pad), ...] -> chunk-major
        batch, n_chunks, chunk_size, *a.shape[2:]
    ).swapaxes(0, 1)
    kernel = head_kernel.astype(compute_dtype)

    @jax.checkpoint
    def chunk_nll_sum(x_c, labels_c, w_c):
        logits = jnp.einsum(
            "btd,dv->btv", x_c, kernel
        )  # [B, chunk, V] — the only logits tile alive
        return jnp.sum(_live_nll(logits, labels_c, w_c) * w_c)

    def body(acc, chunk):
        x_c, labels_c, w_c = chunk
        return acc + chunk_nll_sum(x_c, labels_c, w_c), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32), (split(x_in), split(labels), split(weights))
    )
    return total / jnp.maximum(jnp.sum(weights), 1.0)
