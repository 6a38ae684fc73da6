"""Pipeline-parallel Transformer LM: stages over the mesh ``pipe`` axis.

No reference analog (``SURVEY.md`` §2c: PP absent); this is the workload
driver for ``parallel.pipeline``. The decomposition is the standard one:

- **embed** (token embedding) and **head** (final norm + logits) run outside
  the pipeline as ordinary GSPMD-sharded ops on the full batch;
- the ``num_layers`` transformer blocks split into ``num_stages`` equal
  stages whose parameters live in ONE stacked pytree (leaf ``[S, ...]``,
  sharded over ``pipe``), created by ``jax.vmap`` over per-stage inits;
- activations are split into ``num_microbatches`` and driven through the
  GPipe ``lax.scan``/``ppermute`` schedule of
  :func:`~deeplearning_mpi_tpu.parallel.pipeline.pipeline_apply`.

This is a plain Python model class (not ``nn.Module``) exposing the same
``init(rng, tokens, train=...)`` / ``apply(variables, tokens, ...)`` contract
the trainer consumes (``train.state.create_train_state``), because the
pipeline's param layout — one stacked tree instead of per-layer subtrees —
is easier to state explicitly than to coax out of module transforms.

MoE composes with PP: flax's sown collections cannot cross the
``lax.scan``/``ppermute`` schedule, so each stage's load-balance losses are
collected per apply and carried through the pipeline as one scalar per
microbatch in the activation pytree; ``apply(..., mutable=...)`` re-emits
the microbatch-mean under ``moe.AUX_COLLECTION`` so the trainer's
``collect_aux_loss`` path is identical for pipelined and flat models.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deeplearning_mpi_tpu.models.moe import (
    AUX_COLLECTION,
    DROP_NAME,
    METRIC_COLLECTION,
    collect_aux_loss,
    collect_dropped_fraction,
    mlp_cls_from_config,
)
from deeplearning_mpi_tpu.models.transformer import (
    Block,
    RMSNorm,
    TransformerConfig,
    _remat_block,
)
from deeplearning_mpi_tpu.parallel.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)


class StageBlocks(nn.Module):
    """One pipeline stage: ``num_blocks`` consecutive transformer blocks.

    ``remat`` checkpoints each block (recompute activations in backward) —
    composes with pipelining for the standard PP+remat memory recipe.
    ``mlp_cls`` is the same injection point as :class:`TransformerLM`'s —
    an MoE stage sows its load-balance losses, which the enclosing
    :class:`PipelinedLM` collects per apply and threads through the
    pipeline's activation pytree.
    """

    config: TransformerConfig
    num_blocks: int
    dtype: Any = jnp.bfloat16
    attention_fn: Any = None
    remat: bool | str = False
    mlp_cls: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.config
        if cfg.layers:
            raise NotImplementedError(
                "a pipelined LM over layers of several kinds "
                "(TransformerConfig.layers): a stage does not know which of "
                "the model's layers it holds"
            )
        block_cls = _remat_block(self.remat)
        for i in range(self.num_blocks):
            x = block_cls(
                cfg.num_heads, cfg.head_dim, cfg.d_ff, self.dtype,
                attention_fn=self.attention_fn, mlp_cls=self.mlp_cls,
                num_kv_heads=cfg.num_kv_heads, window=cfg.attention_window,
                name=f"block_{i}",
            )(x, positions)
        return x


class EmbedHead(nn.Module):
    """Embedding in, logits out — the non-pipelined ends of the LM."""

    config: TransformerConfig
    dtype: Any = jnp.bfloat16

    def setup(self) -> None:
        cfg = self.config
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=self.dtype,
            embedding_init=nn.initializers.normal(0.02),
        )
        self.final_norm = RMSNorm()
        if not cfg.tied_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype)

    def encode(self, tokens: jax.Array) -> jax.Array:
        return self.embed(tokens)

    def decode(self, x: jax.Array) -> jax.Array:
        x = self.final_norm(x)
        if self.config.tied_embeddings:
            logits = self.embed.attend(x.astype(self.dtype))
        else:
            logits = self.lm_head(x)
        return logits.astype(jnp.float32)

    def prehead(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(final-norm activations, head kernel) for the chunked head+loss
        path (``ops.loss.chunked_lm_loss``) — tied embeddings only, same
        restriction and rationale as ``TransformerLM.return_prehead``."""
        if not self.config.tied_embeddings:
            raise ValueError("prehead requires tied_embeddings")
        return self.final_norm(x), self.embed.embedding.T

    def __call__(self, tokens: jax.Array) -> jax.Array:
        # Init-only path: touches every param so one ``init`` shapes them all.
        return self.decode(self.encode(tokens))


class PipelinedLM:
    """GPipe-parallel causal LM with the trainer's init/apply contract."""

    def __init__(
        self,
        config: TransformerConfig,
        mesh: jax.sharding.Mesh,
        *,
        num_stages: int | None = None,
        num_microbatches: int = 4,
        dtype: Any = jnp.bfloat16,
        attention_fn: Any = None,
        remat: bool | str = False,
        return_prehead: bool = False,
    ) -> None:
        if return_prehead and not config.tied_embeddings:
            # Same restriction as TransformerLM.return_prehead, rejected at
            # construction like the flat model's init-time check.
            raise ValueError("return_prehead requires tied_embeddings")
        self.return_prehead = return_prehead
        self.config = config
        self.mesh = mesh
        self.num_stages = num_stages or mesh.shape["pipe"]
        if self.num_stages != mesh.shape["pipe"] and mesh.shape["pipe"] != 1:
            raise ValueError(
                f"num_stages {self.num_stages} != mesh pipe size {mesh.shape['pipe']}"
            )
        if config.num_layers % self.num_stages:
            raise ValueError(
                f"num_layers {config.num_layers} not divisible into "
                f"{self.num_stages} stages"
            )
        self.num_microbatches = num_microbatches
        self.dtype = dtype
        self.stage_mod = StageBlocks(
            config, config.num_layers // self.num_stages, dtype, attention_fn,
            remat=remat, mlp_cls=mlp_cls_from_config(config),
        )
        self.embed_head = EmbedHead(config, dtype)

    def init(self, rng: jax.Array, tokens: jax.Array, train: bool = False) -> dict:
        del train
        r_eh, r_st = jax.random.split(rng)
        eh_params = self.embed_head.init(r_eh, tokens)["params"]
        x = jnp.zeros((1, tokens.shape[-1], self.config.d_model), self.dtype)
        pos = jnp.zeros((1, tokens.shape[-1]), jnp.int32)
        stage_params = jax.vmap(
            lambda key: self.stage_mod.init(key, x, pos)["params"]
        )(jax.random.split(r_st, self.num_stages))
        return {"params": {"embed_head": eh_params, "stages": stage_params}}

    def apply(
        self,
        variables: dict,
        tokens: jax.Array,
        positions: jax.Array | None = None,
        *,
        train: bool = False,
        mutable: Any = (),
    ):
        del train
        params = variables["params"]
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[-1], dtype=jnp.int32)[None, :], tokens.shape
            )
        x = self.embed_head.apply(
            {"params": params["embed_head"]}, tokens, method=EmbedHead.encode
        )
        xs = split_microbatches(
            {"x": x, "pos": positions}, self.num_microbatches
        )
        # Sown collections can't cross pipeline_apply's scan/ppermute
        # schedule, so each MoE stage's load-balance losses are collected at
        # apply time and ride the activation pytree as one scalar per
        # microbatch (same-structure in/out contract preserved; a dense model
        # carries the zero scalar at negligible cost).
        xs["aux"] = jnp.zeros((self.num_microbatches,), jnp.float32)
        # The dropped/unserved-token metric rides the same per-microbatch
        # scalar channel (sown collections can't cross the scan/ppermute
        # schedule either); sum of per-stage layer-means, normalized to the
        # all-layer mean below. Presence is trace-static: the cell records
        # whether any stage actually sows (MoE) so dense pipelines emit no
        # metric, mirroring the flat model.
        xs["drop"] = jnp.zeros((self.num_microbatches,), jnp.float32)
        drop_seen: list[bool] = []

        def stage_fn(stage_params, acts):
            y, mutated = self.stage_mod.apply(
                {"params": stage_params}, acts["x"], acts["pos"],
                mutable=[AUX_COLLECTION, METRIC_COLLECTION],
            )
            aux = acts["aux"] + collect_aux_loss(mutated)
            drop = collect_dropped_fraction(mutated)
            if drop is not None and not drop_seen:
                drop_seen.append(True)
            drop = acts["drop"] + (0.0 if drop is None else drop)
            return {"x": y, "pos": acts["pos"], "aux": aux, "drop": drop}

        ys = pipeline_apply(stage_fn, params["stages"], xs, mesh=self.mesh)
        # Mean over microbatches: each microbatch's aux is the sum over
        # stages of its own Switch-style balance loss, so the mean keeps the
        # trainer-facing scale identical to the unpipelined model's
        # full-batch aux (exactly equal when routing statistics are; see
        # tests/test_pipeline.py for the per-microbatch oracle).
        aux_total = jnp.mean(ys.pop("aux"))
        # Per-microbatch drop is a sum of num_stages equal-layer-count stage
        # means, so /num_stages makes it the all-layer mean — the same
        # quantity collect_dropped_fraction reports for the flat model.
        drop_total = jnp.mean(ys.pop("drop")) / self.num_stages
        out = merge_microbatches(ys)["x"]
        head_method = (
            EmbedHead.prehead if self.return_prehead else EmbedHead.decode
        )
        outputs = self.embed_head.apply(
            {"params": params["embed_head"]}, out, method=head_method
        )
        if mutable:
            mutated_out = {AUX_COLLECTION: {"pipeline": aux_total}}
            if drop_seen:
                mutated_out[METRIC_COLLECTION] = {DROP_NAME: drop_total}
            return outputs, mutated_out
        return outputs
