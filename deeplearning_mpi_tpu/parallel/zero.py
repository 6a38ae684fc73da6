"""ZeRO-1 optimizer-state sharding over the ``data`` axis — two schedules.

No reference analog (the reference replicates the full optimizer on every
DDP rank — ``torch.optim.SGD`` at ``pytorch/resnet/main.py:114``); this is
the standard memory lever for large-model data parallelism.

Two implementations of the same semantics live here:

1. **GSPMD annotation** (:func:`zero1_spec`): each optimizer moment leaf is
   sharded over ``data`` on its largest free divisible dim, and the
   partitioner derives the ZeRO-1 communication schedule — reduce-scatter
   of the gradients feeding the sharded update, all-gather of the parameter
   updates — from the placement. Zero code, but the schedule is whatever
   GSPMD emits.
2. **Explicit bucketed schedule** (:func:`make_overlapped_train_step`): a
   ``shard_map`` step that writes that schedule out by hand — gradient
   buckets reduce-scattered as independent collectives
   (``lax.psum_scatter``), the optimizer update run on the 1/dp parameter
   and moment shards, the updated shards all-gathered back. Because each
   bucket is its own collective (instead of one fused GSPMD region), XLA's
   latency-hiding scheduler can slide bucket k's reduce-scatter under
   bucket k+1's gradient math and the tail all-gathers under the next
   step's early forward once steps are dispatched back-to-back.

The two paths are engineered to be **bit-identical** on CPU (asserted in
``tests/test_overlap.py`` and ``make overlap-smoke``), which pins down the
subtle part — loss/gradient reduction structure:

- The differentiated scalar is the *local* sum over the *global*
  denominator (``local_sum / max(psum(count), 1)``). Differentiating
  *through* ``lax.psum`` is wrong under ``check_rep=False``: psum
  transposes to psum, double-counting every gradient — and an optimizer
  like Adam is scale-invariant enough to shrink that 2x error to ~1e-4
  parameter drift, so it must be excluded structurally, not tested for.
- The resulting *partial* per-rank gradients are then explicitly
  reduce-scattered (sharded leaves) or psummed (replicated leaves),
  reproducing GSPMD's partial-sum + all-reduce association exactly.
- The loss *value* is ``psum(local_sum) / den`` carried on the has_aux
  path, where no cotangent flows.

Known bit-level deviation: **tied embeddings**. GSPMD all-reduces the head
and scatter cotangent contributions separately and adds the reduced terms
(``add(all-reduce(dot), all-reduce(scatter))``); a local backward adds the
partials first and reduces once. Same value to ~2 ulp, different
association — bitwise tests use untied configs, tied is covered at
``allclose``.

Second deviation: **the EMA** (``ema_decay > 0``). Params, moments and
losses stay bit-equal, clipped or not, but ``d*ema + (1-d)*params`` of the
ZeRO-sharded leaves does not: XLA:CPU contracts it to one fma inside the
GSPMD step's fusion and rounds twice after this schedule's explicit
``all_gather`` (replicated leaves agree; swapping the operands moves the
difference to them). 7.5e-9 at the first step, 8.9e-8 after five — covered
at ``allclose``; no reordering here can pin another fusion's contraction.

Memory: Adam's ``mu``+``nu`` drop from 2x params replicated to 2x params/dp
per device. Params themselves stay replicated (ZeRO-3 parameter sharding is
a different trade and not implemented here).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import optax
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from deeplearning_mpi_tpu.models.moe import (
    AUX_COLLECTION,
    METRIC_COLLECTION,
    collect_dropped_fraction,
)
from deeplearning_mpi_tpu.ops.loss import (
    _token_nll,
    bce_per_image,
    dice_per_image,
    lm_token_nll,
)
from deeplearning_mpi_tpu.runtime.compat import (
    buffer_donation_supported,
    shard_map,
)
from deeplearning_mpi_tpu.runtime.mesh import AXIS_DATA
from deeplearning_mpi_tpu.train.state import TrainState

#: Leaves smaller than this stay replicated (scalars, counts, tiny biases —
#: sharding them buys nothing and costs collective latency).
MIN_SIZE = 1 << 14

#: Target gradient bytes per reduce-scatter bucket. DDP-style sizing: big
#: enough to amortize collective launch latency, small enough that several
#: independent collectives exist for the latency-hiding scheduler to
#: interleave with compute.
BUCKET_BYTES = 4 << 20


class OverlapUnsupported(ValueError):
    """The overlapped schedule cannot express this configuration.

    Raised by :func:`make_overlapped_train_step` at build time — never
    mid-step — so callers (``Trainer.place_state``) can fall back to the
    GSPMD path with the reason logged.
    """


def zero1_dim(
    leaf: Any,
    base: P,
    dp: int,
    *,
    min_size: int = MIN_SIZE,
) -> int | None:
    """The dim a ZeRO-1 placement shards ``leaf`` on, or None (replicated).

    Picks the largest dim that is free in ``base`` (the leaf's TP/EP/PP
    spec) and divisible by ``dp``; ties break on the first such dim, so the
    choice is deterministic in the leaf's shape alone. Leaves smaller than
    ``min_size`` and leaves with no qualifying dim stay replicated.

    Single source of truth for both schedules: :func:`zero1_spec` (GSPMD)
    and :func:`plan_buckets` (explicit) derive from it, which is what makes
    the explicit schedule's shard slicing line up with the GSPMD placement
    of the optimizer state.
    """
    if dp <= 1 or leaf.size < min_size:
        return None
    dims: list = list(base) + [None] * (leaf.ndim - len(base))
    best = None
    for i, (size, taken) in enumerate(zip(leaf.shape, dims)):
        if taken is None and size % dp == 0:
            if best is None or size > leaf.shape[best]:
                best = i
    return best


def zero1_spec(
    leaf: jax.Array,
    base: P,
    dp: int,
    *,
    data_axis: str = AXIS_DATA,
    min_size: int = MIN_SIZE,
) -> P:
    """Extend ``base`` (the leaf's TP/EP/PP spec) with a ``data``-axis shard.

    Picks the largest dim that is free in ``base`` and divisible by ``dp``;
    returns ``base`` unchanged when none qualifies or the leaf is small.
    """
    best = zero1_dim(leaf, base, dp, min_size=min_size)
    if best is None:
        return base
    dims: list = list(base) + [None] * (leaf.ndim - len(base))
    dims[best] = data_axis
    return P(*dims)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static communication plan for the explicit ZeRO-1 schedule.

    ``shard_dims[i]`` is the shard dim of flat parameter leaf ``i`` (None =
    replicated). ``buckets`` groups the sharded leaf indices into
    byte-bounded reduce-scatter buckets in traversal order; ``replicated``
    lists the leaves that travel in the single residual psum.
    """

    shard_dims: tuple[int | None, ...]
    buckets: tuple[tuple[int, ...], ...]
    replicated: tuple[int, ...]

    @property
    def n_sharded(self) -> int:
        return sum(len(b) for b in self.buckets)


def plan_buckets(
    leaves: list[Any],
    dp: int,
    *,
    bucket_bytes: int = BUCKET_BYTES,
    min_size: int = MIN_SIZE,
) -> BucketPlan:
    """Group parameter leaves into reduce-scatter buckets.

    Deterministic in the flattened leaf order (pytree traversal order), so
    the plan — and therefore the emitted collective schedule — is stable
    across processes and across runs. A leaf larger than ``bucket_bytes``
    gets its own bucket; buckets never split a leaf.
    """
    shard_dims = [zero1_dim(leaf, P(), dp, min_size=min_size) for leaf in leaves]
    buckets: list[tuple[int, ...]] = []
    current: list[int] = []
    current_bytes = 0
    for i, (leaf, d) in enumerate(zip(leaves, shard_dims)):
        if d is None:
            continue
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if current and current_bytes + nbytes > bucket_bytes:
            buckets.append(tuple(current))
            current, current_bytes = [], 0
        current.append(i)
        current_bytes += nbytes
    if current:
        buckets.append(tuple(current))
    replicated = tuple(i for i, d in enumerate(shard_dims) if d is None)
    return BucketPlan(
        shard_dims=tuple(shard_dims),
        buckets=tuple(buckets),
        replicated=replicated,
    )


# ---------------------------------------------------------------------------
# Mirrored losses: local-sum / global-denominator form.
#
# Each task's loss is a sum of global means. A term is (local_sum,
# local_weight_sum | None, local_count): the global mean is
# psum(local_sum) / max(psum(weight_sum), 1) for masked terms and
# psum(local_sum) / global_count for plain means — and the *differentiated*
# scalar per rank is local_sum / that same global denominator, which gives
# every element exactly the cotangent the GSPMD mean gives it while keeping
# psum out of the differentiated path (see module docstring).
# ---------------------------------------------------------------------------

_LossTerms = Callable[[Any, dict[str, jax.Array]], list[tuple]]


def _mirrored_loss_terms(task: str, seg_loss: str) -> _LossTerms:
    if task == "lm":

        def lm_terms(outputs, chunk):
            nll, w = lm_token_nll(outputs, chunk["tokens"], chunk.get("mask"))
            return [(jnp.sum(nll * w), jnp.sum(w), nll.size)]

        return lm_terms
    if task == "classification":

        def cls_terms(outputs, chunk):
            nll = _token_nll(outputs, chunk["label"])
            return [(jnp.sum(nll), None, nll.size)]

        return cls_terms
    if task == "segmentation":
        if seg_loss not in ("bce", "dice", "bce_dice"):
            raise ValueError(f"unknown seg_loss '{seg_loss}'")

        def seg_terms(outputs, chunk):
            logits, targets = outputs[..., 0], chunk["mask"]
            terms = []
            if seg_loss in ("bce", "bce_dice"):
                per = bce_per_image(logits, targets)
                terms.append((jnp.sum(per), None, per.size))
            if seg_loss in ("dice", "bce_dice"):
                per = dice_per_image(logits, targets)
                terms.append((jnp.sum(per), None, per.size))
            return terms

        return seg_terms
    raise ValueError(f"unknown task '{task}'")


def _check_supported(
    task: str,
    state: TrainState,
    mesh: Mesh,
    *,
    data_axis: str,
    aux_weight: float,
    loss_chunk: int,
) -> int:
    """Factory-time feasibility gate; returns dp. Raises OverlapUnsupported
    with the reason — the caller logs it and stays on the GSPMD path."""
    dp = int(mesh.shape.get(data_axis, 1))
    if dp <= 1:
        raise OverlapUnsupported(
            f"'{data_axis}' axis has size {dp} — no data parallelism to overlap"
        )
    busy = [a for a in mesh.axis_names if a != data_axis and mesh.shape[a] > 1]
    if busy:
        raise OverlapUnsupported(
            f"non-data mesh axes in use ({busy}) — composed TP/EP/PP stays "
            "on the GSPMD path"
        )
    if aux_weight:
        raise OverlapUnsupported(
            "aux_weight != 0: the MoE load-balance loss spans all routed "
            "tokens and its cross-chunk folding is GSPMD-only"
        )
    if loss_chunk:
        raise OverlapUnsupported(
            "loss_chunk > 0: the chunked head+loss path is GSPMD-only"
        )
    if jax.tree_util.tree_leaves(state.batch_stats):
        raise OverlapUnsupported(
            "model carries batch_stats (BatchNorm) — local-statistics "
            "mutation is GSPMD-only"
        )
    if task not in ("lm", "classification", "segmentation"):
        raise OverlapUnsupported(f"unknown task '{task}'")
    return dp


def _probe_sharded_update(state: TrainState, plan: BucketPlan, dp: int) -> None:
    """Shape-check ``tx.update`` on the 1/dp shard trees, at build time.

    The explicit schedule assumes the optimizer state *mirrors* parameter
    shapes (Adam/SGD/Lion moments do; Adafactor's factored moments do not),
    so the elementwise update can run on matching shards. eval_shape proves
    it cheaply; any failure becomes OverlapUnsupported, never a mid-step
    shape error.
    """

    def shard(leaf, d):
        if d is None or not hasattr(leaf, "shape"):
            return leaf
        shape = list(leaf.shape)
        shape[d] //= dp
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    flat_p, treedef = jtu.tree_flatten(state.params)
    local_p = treedef.unflatten(
        [shard(leaf, d) for leaf, d in zip(flat_p, plan.shard_dims)]
    )
    local_opt = jax.tree_util.tree_map(
        lambda leaf: shard(leaf, zero1_dim(leaf, P(), dp))
        if hasattr(leaf, "shape")
        else leaf,
        state.opt_state,
    )
    try:
        out_u, out_opt = jax.eval_shape(state.tx.update, local_p, local_opt, local_p)
    except Exception as e:  # noqa: BLE001 — any trace failure means "unsupported"
        raise OverlapUnsupported(
            "optimizer state does not mirror parameter shapes (adafactor-"
            f"style factored moments?) — sharded update fails to trace: {e}"
        ) from e
    in_shapes = [
        leaf.shape for leaf in jtu.tree_leaves(local_opt) if hasattr(leaf, "shape")
    ]
    out_shapes = [
        leaf.shape for leaf in jtu.tree_leaves(out_opt) if hasattr(leaf, "shape")
    ]
    if in_shapes != out_shapes:
        raise OverlapUnsupported(
            "optimizer update changes its state's shapes under sharding — "
            "the explicit ZeRO-1 schedule requires a shape-preserving update"
        )


def make_overlapped_train_step(
    task: str,
    state: TrainState,
    mesh: Mesh,
    *,
    donate: bool = True,
    aux_weight: float = 0.0,
    grad_accum: int = 1,
    loss_chunk: int = 0,
    seg_loss: str = "bce",
    ema_decay: float = 0.0,
    clip_norm: float | None = None,
    bucket_bytes: int = BUCKET_BYTES,
    data_axis: str = AXIS_DATA,
) -> Callable[[TrainState, dict], tuple[TrainState, dict[str, jax.Array]]]:
    """Build the explicit bucketed ZeRO-1 train step (shard_map).

    Drop-in for ``train.trainer.make_train_step`` on pure-DP meshes with
    ZeRO-1 placement: same ``(state, batch) -> (state, metrics)`` signature,
    same NaN-skip / EMA / metric semantics, bit-identical params, optimizer
    state and losses to the GSPMD path on CPU (untied params; see module
    docstring for the tied-embedding and EMA caveats). Raises
    :class:`OverlapUnsupported` at build time for configurations the
    schedule cannot express — callers fall back to GSPMD.

    ``state`` is the placement template: the step must be called with states
    of the same treedef (the Trainer passes its own ``self.state``), already
    placed by ``parallel.shard_state(..., zero=True)``. ``clip_norm`` must
    echo the value baked into ``state.tx``: the true global-norm clip is
    applied *before* the sharded update (each rank only holds 1/dp of the
    gradient, so the chain's own clip would see a partial norm); after the
    pre-clip, the inner ``optax.clip_by_global_norm`` sees a norm within
    bounds and passes gradients through unchanged.

    ``grad_accum > 1`` accumulates over chunks of the *local* batch (the
    GSPMD path chunks the global batch; chunking locally avoids cross-rank
    data movement). The combined gradient is algebraically identical —
    every token keeps exactly the weight the full-batch masked mean gives
    it — but the floating-point association differs, so bit-equality claims
    hold for ``grad_accum=1`` and accumulation is covered at ``allclose``.
    """
    dp = _check_supported(
        task, state, mesh,
        data_axis=data_axis, aux_weight=aux_weight, loss_chunk=loss_chunk,
    )
    if ema_decay and state.ema_params is None:
        raise ValueError(
            "ema_decay set but the state tracks no EMA — build it "
            "with create_train_state(..., ema=True)"
        )
    donate = donate and buffer_donation_supported()
    terms_fn = _mirrored_loss_terms(task, seg_loss)

    from deeplearning_mpi_tpu.train.trainer import _INPUTS

    input_key = _INPUTS[task]

    flat_params, params_treedef = jtu.tree_flatten(state.params)
    plan = plan_buckets(flat_params, dp, bucket_bytes=bucket_bytes)
    _probe_sharded_update(state, plan, dp)

    # in/out specs: params & step replicated, optimizer moments on their
    # ZeRO-1 placement — matching infer_state_sharding(zero=True), so the
    # same placed state feeds either step implementation. Built from the
    # template's treedef: TrainState embeds static fields (apply_fn, tx), so
    # a spec tree only matches states sharing the template's structure.
    def _state_specs(s: TrainState):
        def spec(path, leaf):
            if ".opt_state" in jtu.keystr(path):
                return zero1_spec(leaf, P(), dp, data_axis=data_axis)
            return P()

        return jtu.tree_map_with_path(spec, s)

    state_specs = _state_specs(state)

    def global_mean_terms(outputs, chunk):
        """[(local_sum, global_denominator)] per loss term."""
        out = []
        for local_sum, w_sum, n_local in terms_fn(outputs, chunk):
            if w_sum is None:
                den = jnp.asarray(float(n_local * dp), jnp.float32)
            else:
                den = jnp.maximum(lax.psum(w_sum, data_axis), 1.0)
            out.append((local_sum, den))
        return out

    def body(st: TrainState, batch: dict) -> tuple[TrainState, dict]:
        moe_drop_seen: list[bool] = []

        def loss_and_grads(chunk, data_scale=None):
            def compute_loss(params):
                outputs, mutated = st.apply_fn(
                    {"params": params, "batch_stats": st.batch_stats},
                    chunk[input_key],
                    train=True,
                    mutable=["batch_stats", AUX_COLLECTION, METRIC_COLLECTION],
                )
                terms = global_mean_terms(outputs, chunk)
                # Differentiate the LOCAL sums over the GLOBAL denominators;
                # the global loss value rides the aux path (no cotangent
                # flows into its psum).
                local = sum(s / den for s, den in terms)
                loss = sum(lax.psum(s, data_axis) / den for s, den in terms)
                total = local if data_scale is None else data_scale * local
                drop = collect_dropped_fraction(mutated)
                if drop is not None and not moe_drop_seen:
                    moe_drop_seen.append(True)
                if drop is None:
                    drop = jnp.zeros((), jnp.float32)
                else:
                    # Equal-sized shards: mean of per-rank means == global.
                    drop = lax.psum(drop, data_axis) / dp
                return total, (loss, drop)

            (_, aux), grads = jax.value_and_grad(compute_loss, has_aux=True)(
                st.params
            )
            return *aux, grads

        if grad_accum == 1:
            loss, drop_frac, partial_grads = loss_and_grads(batch)
        else:
            def split(path, x):
                if x.shape[0] % grad_accum:
                    raise ValueError(
                        f"per-device batch dim of batch[{jtu.keystr(path)!r}] "
                        f"(shape {tuple(x.shape)}) not divisible by "
                        f"grad_accum={grad_accum}"
                    )
                return x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:])

            chunks = jtu.tree_map_with_path(split, batch)
            # Global valid-element weight of the FULL batch — each chunk's
            # scale is final before the scan, exactly like the GSPMD path.
            if task == "lm" and batch.get("mask") is not None:
                w_full = jnp.sum(batch["mask"][:, 1:].astype(jnp.float32))
                w_total = jnp.maximum(lax.psum(w_full, data_axis), 1.0)
            else:
                w_total = float(grad_accum)

            def accum(carry, chunk):
                grad_sum, loss_sum, drop_sum = carry
                if task == "lm" and chunk.get("mask") is not None:
                    w_chunk = lax.psum(
                        jnp.sum(chunk["mask"][:, 1:].astype(jnp.float32)),
                        data_axis,
                    )
                else:
                    w_chunk = jnp.asarray(1.0, jnp.float32)
                w = w_chunk / w_total
                loss, drop, grads = loss_and_grads(chunk, data_scale=w)
                grad_sum = jax.tree_util.tree_map(jnp.add, grad_sum, grads)
                return (
                    grad_sum, loss_sum + w * loss, drop_sum + drop / grad_accum,
                ), None

            zero_grads = jax.tree_util.tree_map(jnp.zeros_like, st.params)
            (partial_grads, loss, drop_frac), _ = jax.lax.scan(
                accum,
                (zero_grads, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                chunks,
            )

        flat_g = params_treedef.flatten_up_to(partial_grads)
        flat_p = params_treedef.flatten_up_to(st.params)
        idx = lax.axis_index(data_axis)

        # Bucketed reduce-scatter of the partial gradients: one collective
        # per bucket, each independent — the latency-hiding scheduler's raw
        # material. Each rank keeps the 1/dp shard co-located with its
        # optimizer-state shard; the replicated residue rides one psum.
        g_shard: list = [None] * len(flat_g)
        p_shard: list = [None] * len(flat_p)
        for bucket in plan.buckets:
            moved = [
                jnp.moveaxis(flat_g[i], plan.shard_dims[i], 0) for i in bucket
            ]
            scattered = lax.psum_scatter(
                moved, data_axis, scatter_dimension=0, tiled=True
            )
            for i, s in zip(bucket, scattered):
                d = plan.shard_dims[i]
                g_shard[i] = jnp.moveaxis(s, 0, d)
                n = flat_p[i].shape[d] // dp
                p_shard[i] = lax.dynamic_slice_in_dim(flat_p[i], idx * n, n, axis=d)
        if plan.replicated:
            summed = lax.psum([flat_g[i] for i in plan.replicated], data_axis)
            for i, s in zip(plan.replicated, summed):
                g_shard[i] = s
                p_shard[i] = flat_p[i]

        if clip_norm is not None:
            # True global-norm clip over the *sharded* gradients, mirroring
            # optax.clip_by_global_norm leaf-for-leaf: per-leaf sum of
            # squares (one psum for the sharded leaves — disjoint shards sum
            # to the full leaf), python-sum in tree order, sqrt, and the
            # same trigger/select form. The chain's own clip then sees a
            # norm <= clip_norm and passes through.
            sumsq = [None] * len(g_shard)
            sharded = [i for i, d in enumerate(plan.shard_dims) if d is not None]
            if sharded:
                reduced = lax.psum(
                    [jnp.sum(jnp.square(g_shard[i])) for i in sharded], data_axis
                )
                for i, r in zip(sharded, reduced):
                    sumsq[i] = r
            for i in plan.replicated:
                sumsq[i] = jnp.sum(jnp.square(g_shard[i]))
            g_norm = jnp.sqrt(sum(sumsq))
            trigger = g_norm < clip_norm
            clip = lambda t: lax.select(  # noqa: E731 — optax's exact form
                trigger, t, (t / g_norm.astype(t.dtype)) * clip_norm
            )
            g_shard = [clip(g) for g in g_shard]

        g_tree = jtu.tree_unflatten(params_treedef, g_shard)
        p_tree = jtu.tree_unflatten(params_treedef, p_shard)

        # 1/dp-sharded optimizer update: each rank updates only its shard of
        # every moment and parameter — ZeRO-1's memory and compute saving.
        updates, new_opt_state = st.tx.update(g_tree, st.opt_state, p_tree)
        new_local = optax.apply_updates(p_tree, updates)

        # All-gather the updated shards back to full parameters — the tail
        # collectives XLA overlaps with the next step's head once dispatched.
        flat_new = params_treedef.flatten_up_to(new_local)
        gathered = list(flat_new)
        for i, d in enumerate(plan.shard_dims):
            if d is not None:
                gathered[i] = lax.all_gather(flat_new[i], data_axis, axis=d, tiled=True)
        new_params = jtu.tree_unflatten(params_treedef, gathered)

        # NaN/Inf guard + EMA: same semantics as make_train_step.
        finite = jnp.isfinite(loss)
        keep = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
            lambda n, o: jnp.where(finite, n, o), new, old
        )
        ema = st.ema_params
        if ema_decay:
            ema = keep(
                jax.tree_util.tree_map(
                    lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                    ema, new_params,
                ),
                ema,
            )
        metrics = {"loss": loss, "finite": jnp.asarray(finite, jnp.float32)}
        if moe_drop_seen:
            metrics["moe_dropped_frac"] = drop_frac
        return (
            st.replace(
                step=st.step + 1,
                params=keep(new_params, st.params),
                opt_state=keep(new_opt_state, st.opt_state),
                ema_params=ema,
            ),
            metrics,
        )

    # The batch's pytree structure is unknown until the first call; build
    # (and cache) the jitted shard_map per batch treedef. Batch leaves are
    # sharded on their leading (batch) dim.
    compiled: dict[Any, Callable] = {}

    def step(st: TrainState, batch: dict):
        key = jtu.tree_structure(batch)
        fn = compiled.get(key)
        if fn is None:
            batch_specs = jax.tree_util.tree_map(lambda _: P(data_axis), batch)
            fn = jax.jit(
                shard_map(
                    body,
                    mesh=mesh,
                    in_specs=(state_specs, batch_specs),
                    out_specs=(state_specs, P()),
                    check_vma=False,
                ),
                donate_argnums=(0,) if donate else (),
            )
            compiled[key] = fn
        return fn(st, batch)

    step.bucket_plan = plan  # introspection for tests / bench provenance
    return step
