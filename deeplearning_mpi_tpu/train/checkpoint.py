"""Checkpoint/resume via Orbax.

The reference checkpoints by overwriting one ``.pth`` with the DDP-prefixed
``state_dict`` from rank 0, losing optimizer state and step count; resume
reloads weights only and restarts at epoch 0 (``pytorch/resnet/main.py:48-52,
136-139``, ``pytorch/unet/train.py:72-74,213-216``; SURVEY.md §5.4). This
checkpointer saves the **full** train state (params + BN stats + optimizer
state + step) with Orbax — sharded save/restore, every host participating,
process 0 coordinating — and keeps a history of steps instead of overwriting.
The ``cuda:0 → cuda:LOCAL_RANK`` map_location remap the reference needs
(``resnet/main.py:49``) has no analog: Orbax restores arrays directly into
their target shardings.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any

import jax
import orbax.checkpoint as ocp

from deeplearning_mpi_tpu.analysis import sanitizer as _sanitizer
from deeplearning_mpi_tpu.resilience.integrity import (
    CheckpointCorruption,
    atomic_write_json,
    corrupt_checkpoint,
    dir_digests,
    read_manifest,
    write_manifest,
)
from deeplearning_mpi_tpu.train.state import TrainState


class Checkpointer:
    """Save/restore the full train state under ``directory``.

    The epoch is stored as the checkpoint step label, so resume can continue
    the epoch loop where it stopped — unlike the reference, which always
    restarts at epoch 0 with a fresh optimizer.

    Two layers of durability (``docs/RESILIENCE.md``):

    - **Atomicity + retention** — Orbax writes each step into a temporary
      directory and renames it into place on commit, so a mid-save kill
      leaves the previous step intact, never a half-written latest; the
      manager's ``max_to_keep`` bounds history instead of growing without
      limit (the reference overwrote one ``.pth`` in place — atomic never,
      history never).
    - **Integrity manifests** — every save also writes a sha256-per-file
      manifest of the committed step beside the step dir (atomic write,
      :mod:`..resilience.integrity`), and :meth:`restore_verified`
      re-hashes the files BEFORE asking Orbax to read them, rolling back
      to the newest step whose digests match. File-level verification is
      load-bearing twice over: corrupt bytes never reach tensorstore's
      chunk decoder (a mid-read decompression failure has been observed to
      poison the process), and hashing the files requires the async write
      to have landed, which closes a donated-buffer race (see
      :meth:`save`). Manifests are single-process-only (``integrity``
      auto-disables on multi-host, where hosts write disjoint shards);
      steps without a manifest (pre-integrity history) restore unverified
      rather than failing.

    ``chaos`` accepts a :class:`~..resilience.faults.ChaosInjector`; a
    planned ``corrupt_ckpt@epoch:N`` flips bytes inside the just-committed
    step so the verify-and-roll-back path is tested against real damage.

    **Last-known-good pinning** (numerics guardrails, docs/RESILIENCE.md):
    with integrity on, the newest save that still hashes clean AFTER the
    chaos-corruption hook is pinned in ``last_good.json``. Retention is
    done manually here, never by Orbax: the keep set is the newest
    ``max_to_keep`` steps **plus the pin** — the retention bug this
    replaces let Orbax's count window silently delete the only verified
    checkpoint while every younger one was corrupt.
    :meth:`rollback_to_last_good` restores the pin, DELETES every younger
    step (they contain the poisoned updates), and bumps the pin's
    monotonic ``generation`` — the anti-rollback fence: a pin file that
    ever goes backward in generation within one process's lifetime means
    someone swapped in a stale pin to smuggle old weights past the
    rollback, and the checkpointer refuses it loudly.
    """

    def __init__(
        self,
        directory: str | Path,
        *,
        max_to_keep: int = 3,
        chaos: Any = None,
        integrity: bool = True,
    ) -> None:
        self.directory = Path(directory).absolute()
        self.chaos = chaos
        self.integrity = integrity and jax.process_count() == 1
        self.max_to_keep = max_to_keep
        #: anti-rollback fence: highest last-good generation seen; None
        #: until the pin file is first read.
        self._generation: int | None = None
        # Retention is OURS (see class docstring): Orbax's max_to_keep
        # cannot be taught to keep the pinned last-known-good step.
        self.manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=None, create=True
            ),
        )

    def save(self, state: TrainState, *, epoch: int) -> None:
        # Static fields (apply_fn, tx) are not data; persist arrays only.
        # Async: Orbax serializes in the background while training continues;
        # ordering across saves is the manager's job, and close() (and any
        # restore) barriers before process exit.
        # Donation canary (DMT_SANITIZE=1): hash a state leaf before the
        # save, re-verify after the write barrier — the donated-buffer
        # aliasing race described under ``integrity`` below flips the
        # canary where it used to flip checkpoint bytes silently.
        canary = _sanitizer.donation_canary(state) if _sanitizer.enabled() else None
        self.manager.save(
            epoch, args=ocp.args.StandardSave(_arrays_only(state))
        )
        if self.integrity:
            # Barrier, then hash the committed files. The wait is
            # correctness, not just sequencing: the trainer DONATES the
            # state into the next step (trainer.py donate_argnums), and on
            # CPU a jax array is a zero-copy view of the XLA buffer — an
            # async serializer still holding views when the next step
            # reuses those buffers in place writes the *future* state's
            # bytes into this epoch's files (observed under suite load as
            # every digest mismatching on restore). Single-process only,
            # so multi-host TPU keeps the fully-async cadence.
            self.manager.wait_until_finished()
            write_manifest(
                self.directory, epoch,
                dir_digests(self.directory / str(epoch)),
            )
            self._prune_manifests(keep_also=epoch)
        if canary is not None:
            if not self.integrity:
                # The canary needs the same barrier integrity takes: the
                # aliasing race only resolves once the serializer is done.
                self.manager.wait_until_finished()
            canary.verify(state)
        if self.chaos is not None and self.chaos.should_corrupt(epoch=epoch):
            # Chaos: damage the committed step. Must barrier first — flipping
            # bytes under an in-flight async writer tests a race, not
            # integrity checking. (The corruption lands AFTER the manifest
            # was written, so restore sees a mismatch — the point.)
            self.manager.wait_until_finished()
            victim = corrupt_checkpoint(self.directory / str(epoch))
            print(f"chaos: corrupted checkpoint epoch {epoch} ({victim.name})")
        if self.integrity:
            # Pin AFTER the chaos hook, by re-hashing: only a save whose
            # bytes still match its manifest becomes the last-known-good —
            # a corrupted save must never be what rollback lands on.
            manifest = read_manifest(self.directory, epoch)
            if manifest is not None and dir_digests(
                self.directory / str(epoch)
            ) == manifest:
                self._pin(epoch)
        self._prune_retained(keep_also=epoch)

    def latest_epoch(self) -> int | None:
        return self.manager.latest_step()

    # -- last-known-good pin + manual retention -----------------------------
    def _pin_path(self) -> Path:
        return self.directory / "last_good.json"

    def _load_pin(self) -> dict | None:
        """Read ``last_good.json`` through the anti-rollback fence: the
        on-disk generation must never be OLDER than one this process has
        already seen — a backward jump means the pin was swapped for a
        stale copy (the classic anti-rollback attack on A/B firmware
        slots), and trusting it would resurrect checkpoints the rollback
        deliberately discarded."""
        try:
            data = json.loads(self._pin_path().read_text())
        except (OSError, ValueError):
            return None
        if not isinstance(data, dict) or "epoch" not in data:
            return None
        gen = int(data.get("generation", 0))
        if self._generation is not None and gen < self._generation:
            raise CheckpointCorruption(
                f"anti-rollback fence: on-disk last-good generation {gen} "
                f"is older than this process's {self._generation} — "
                f"{self._pin_path()} was replaced with a stale pin"
            )
        self._generation = gen
        return data

    def _pin(self, epoch: int) -> None:
        atomic_write_json(
            self._pin_path(),
            {"epoch": epoch, "generation": self._generation or 0},
        )

    def last_good_epoch(self) -> int | None:
        """The pinned digest-verified epoch, or None (no pin yet)."""
        pin = self._load_pin()
        return int(pin["epoch"]) if pin is not None else None

    def _prune_retained(self, *, keep_also: int) -> None:
        """Manual retention: drop all but the newest ``max_to_keep`` steps,
        ALWAYS keeping the pinned last-known-good — the whole point of
        owning retention (a run where every younger save is corrupt must
        still be able to roll back to the pin, however old)."""
        if not self.max_to_keep:
            return
        steps = sorted(set(self.manager.all_steps()) | {keep_also})
        keep = set(steps[-self.max_to_keep:])
        pin = self.last_good_epoch() if self.integrity else None
        if pin is not None:
            keep.add(pin)
        doomed = [s for s in steps if s not in keep]
        if not doomed:
            return
        # Deleting under an in-flight async save is a hazard; barrier first.
        self.manager.wait_until_finished()
        for step in doomed:
            self.manager.delete(step)
        if self.integrity:
            self._prune_manifests(keep_also=keep_also)

    def rollback_to_last_good(self, template: TrainState) -> tuple[TrainState, int]:
        """Restore the pinned last-known-good checkpoint, DELETE every
        younger step, and bump the anti-rollback generation; returns
        ``(state, epoch)``.

        The guardrails' ``poisoned`` recovery path (docs/RESILIENCE.md):
        younger checkpoints may contain the poisoned updates — unlike
        :meth:`restore_verified`'s walk, which would happily resume from a
        bytes-clean-but-numerically-poisoned newer save, this discards
        them. The pin is still re-verified before restore (pin → corrupt
        since save is possible); a missing or corrupt pin falls back to
        the verified walk. The generation bump makes the rollback
        irreversible on disk: any later appearance of a lower generation
        trips the fence in :meth:`_load_pin`.
        """
        self.manager.wait_until_finished()
        state: TrainState | None = None
        epoch: int | None = None
        pin = self._load_pin() if self.integrity else None
        if pin is not None and int(pin["epoch"]) in set(self.manager.all_steps()):
            epoch = int(pin["epoch"])
            manifest = read_manifest(self.directory, epoch)
            if manifest is None or dir_digests(
                self.directory / str(epoch)
            ) == manifest:
                try:
                    restored = self.manager.restore(
                        epoch,
                        args=ocp.args.StandardRestore(_arrays_only(template)),
                    )
                    state = template.replace(**restored)
                except Exception as err:  # noqa: BLE001 — unreadable = corrupt
                    self._note_corrupt(epoch, f"restore failed: {err}")
            else:
                self._note_corrupt(epoch, "pinned step no longer hashes clean")
        if state is None:
            # No pin (or it died since save): the verified walk is the best
            # remaining evidence of a good state.
            state, epoch = self.restore_verified(template)
        assert epoch is not None
        for step in sorted(self.manager.all_steps(), reverse=True):
            if step > epoch:
                print(
                    f"rollback: discarding checkpoint epoch {step} "
                    f"(younger than last-good {epoch})"
                )
                self.manager.delete(step)
        if self.integrity:
            self._prune_manifests(keep_also=epoch)
            self._generation = (self._generation or 0) + 1
            self._pin(epoch)
        return state, epoch

    def _prune_manifests(self, *, keep_also: int | None = None) -> None:
        """Drop manifests for steps the manager has retired, so retention
        bounds the manifest files the same way it bounds step dirs. The
        just-saved epoch may not appear in ``all_steps()`` until its async
        commit lands — keep it explicitly."""
        keep = set(self.manager.all_steps())
        if keep_also is not None:
            keep.add(keep_also)
        pin = self.last_good_epoch()
        if pin is not None:
            keep.add(pin)  # the pinned step's manifest must outlive the window
        for mf in self.directory.glob("manifest-*.json"):
            try:
                epoch = int(mf.stem.split("-", 1)[1])
            except (IndexError, ValueError):
                continue
            if epoch not in keep:
                mf.unlink(missing_ok=True)

    def restore_verified(
        self, template: TrainState
    ) -> tuple[TrainState, int]:
        """Restore the newest checkpoint that passes digest verification,
        walking backward past corrupted steps; returns ``(state, epoch)``.

        Per candidate, newest first: the step's files are re-hashed against
        its manifest FIRST — a mismatch never reaches Orbax's decoder (a
        tensorstore read of corrupt compressed chunks is a process hazard,
        not a clean exception) — and a restore that *raises* anyway (torn
        metadata, missing arrays) is treated the same way. Both are
        corruption — recorded as a rollback when a chaos injector planned
        it — and the walk continues. A step with no manifest restores
        unverified (legacy history). Exhausting every step raises
        :class:`CheckpointCorruption`: starting over from init is the
        caller's policy decision, not this method's.
        """
        self.manager.wait_until_finished()
        steps = sorted(self.manager.all_steps(), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        for epoch in steps:
            if self.integrity:
                manifest = read_manifest(self.directory, epoch)
                if manifest is not None:
                    actual = dir_digests(self.directory / str(epoch))
                    if actual != manifest:
                        bad = sorted(
                            set(manifest) ^ set(actual)
                            | {k for k in manifest if actual.get(k) != manifest[k]}
                        )
                        self._note_corrupt(
                            epoch,
                            f"digest mismatch in {len(bad)} file(s), e.g. {bad[0]}",
                        )
                        continue
            try:
                restored = self.manager.restore(
                    epoch, args=ocp.args.StandardRestore(_arrays_only(template))
                )
            except Exception as err:  # noqa: BLE001 — unreadable = corrupt
                self._note_corrupt(epoch, f"restore failed: {err}")
                continue
            if self.integrity:
                pin = self._load_pin()
                if pin is not None and epoch < int(pin["epoch"]):
                    # The walk landed BELOW the pin: the pinned step itself
                    # failed (deleted or corrupt since save). Re-pin to what
                    # actually restored so retention protects it from here.
                    self._pin(epoch)
            return template.replace(**restored), epoch
        raise CheckpointCorruption(
            f"no checkpoint under {self.directory} survived verification "
            f"(tried epochs {steps})"
        )

    def restore_elastic(
        self, template: TrainState, *, registry: Any = None
    ) -> tuple[TrainState, int]:
        """Digest-verified restore onto a template built for a DIFFERENT
        dp/ZeRO world size than the one that saved; ``(state, epoch)``.

        The elastic-pod resume path: a checkpoint written by a world of N
        hosts must restore onto the survivors' smaller mesh. This works
        because the GLOBAL shapes are world-size invariant — dp/ZeRO only
        changes how leaves are laid out across devices — and orbax's
        ``StandardRestore`` takes the template's arrays as the abstract
        target, re-sharding every leaf to the NEW mesh's placement as it
        reads (``restore`` docstring: template shardings, not the shardings
        recorded at save time, win). So the whole digest-verified rollback
        walk of :meth:`restore_verified` is reused verbatim; what this
        method adds is the elastic contract made explicit:

        - every restored leaf is ASSERTED to land on the template's
          sharding — a leaf silently left on the saved-world layout would
          train correctly until the first collective, then deadlock or
          reshard per-step;
        - the resharding is counted (``elastic_restore_total``) so a pod
          that recovered via a world-size change is visible in telemetry.

        Batch-order determinism rides on the loader, not this method: the
        global shuffle is a function of (seed, epoch) only
        (``ShardedLoader._epoch_order``), so the resumed smaller world
        consumes the SAME global batch sequence a clean run at that world
        size would — which is what makes elastic resume bit-identical to a
        clean from-checkpoint run (``tests/test_multiprocess.py``).
        """
        state, epoch = self.restore_verified(template)
        mismatched: list[str] = []

        def check(path, t, r):
            if (
                hasattr(t, "sharding")
                and hasattr(r, "sharding")
                and not t.sharding.is_equivalent_to(r.sharding, t.ndim)
            ):
                mismatched.append(jax.tree_util.keystr(path))

        jax.tree_util.tree_map_with_path(
            check, _arrays_only(template), _arrays_only(state)
        )
        if mismatched:
            raise RuntimeError(
                "elastic restore left leaves on the saved world's sharding "
                f"instead of the template's: {mismatched[:5]}"
                + ("..." if len(mismatched) > 5 else "")
            )
        if registry is not None:
            registry.counter("elastic_restore_total").inc()
        return state, epoch

    def _note_corrupt(self, epoch: int, why: str) -> None:
        print(f"checkpoint epoch {epoch} CORRUPT — rolling back ({why})")
        if self.chaos is not None:
            self.chaos.record_rollback("corrupt_ckpt", at=epoch)

    def restore(self, template: TrainState, *, epoch: int | None = None) -> TrainState:
        """Restore into the shardings/dtypes of ``template`` (a freshly
        created state — supplies apply_fn/tx, which are code, not data)."""
        restored = self.manager.restore(
            self._resolve_epoch(epoch),
            args=ocp.args.StandardRestore(_arrays_only(template)),
        )
        return template.replace(**restored)

    def restore_params_only(
        self, template: TrainState, *, epoch: int | None = None
    ) -> TrainState:
        """Restore the weights (params/batch_stats/step, plus the EMA subtree
        when the template tracks one) WITHOUT reading the optimizer state.

        Inference needs weights, not moments — restoring through the
        full-state path forces serving to reconstruct the training run's
        exact optax tree (family AND hyperparameters: adafactor with a
        nonzero ``weight_decay_rate`` appends a transform element, changing
        the tuple arity). Orbax partial restore skips the ``opt_state``
        subtree entirely — its bytes are never read — so the returned
        state keeps the template's (trivial) opt_state; serving templates
        pass ``optax.identity()`` and pay no moment-init memory at all.

        The EMA guard is correctness-bearing in BOTH directions, because
        partial restore cannot fail on the subtree by itself: a template
        without ``ema_params`` simply never asks for it (a forgotten
        ``--ema`` would silently serve the raw last-step weights), and a
        template WITH it against an EMA-less checkpoint silently keeps the
        template's freshly-initialized copy (measured: orbax 0.11 leaves a
        requested-but-absent key untouched instead of erroring). Both
        mismatches are refused loudly against the checkpoint's actual
        saved-tree keys before any bytes are read.
        """
        epoch = self._resolve_epoch(epoch)
        saved = self._saved_tree_keys(epoch)
        if template.ema_params is not None and "ema_params" not in saved:
            raise ValueError(
                "checkpoint has no EMA weights (trained without --ema) but "
                "the restore template tracks an EMA subtree — drop --ema"
            )
        if template.ema_params is None and "ema_params" in saved:
            raise ValueError(
                "checkpoint carries EMA weights (trained with --ema) "
                "but the restore template has no EMA subtree — pass "
                "--ema to serve the averaged weights"
            )
        item: dict[str, Any] = {
            "step": template.step,
            "params": template.params,
            "batch_stats": template.batch_stats,
        }
        if template.ema_params is not None:
            item["ema_params"] = template.ema_params
        # Template shardings travel via restore_args; without them orbax
        # would fall back to the shardings recorded at save time (wrong
        # topology for --tp serving of a 1-device-trained checkpoint).
        restore_args = ocp.checkpoint_utils.construct_restore_args(item)
        args = ocp.args.PyTreeRestore(
            item=item, restore_args=restore_args, partial_restore=True
        )
        restored = self.manager.restore(epoch, args=args)
        return template.replace(**restored)

    def _resolve_epoch(self, epoch: int | None) -> int:
        self.manager.wait_until_finished()  # in-flight async save must land first
        if epoch is None:
            epoch = self.manager.latest_step()
        if epoch is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        return epoch

    def _saved_tree_keys(self, epoch: int) -> set[str]:
        """Top-level keys of the saved tree.

        Read through a short-lived metadata-only manager: ``item_metadata``
        needs a handler registry, but registering one on ``self.manager``
        pins its args types to Standard* and rejects the PyTreeRestore that
        partial restore requires (measured on orbax 0.11). The manager owns
        step-path resolution, so no on-disk layout is hardcoded here.
        Fail-loud on an unreadable tree: the EMA guard above is
        correctness-bearing, not advisory.
        """
        probe = ocp.CheckpointManager(
            self.directory, item_handlers=ocp.StandardCheckpointHandler()
        )
        try:
            return set(probe.item_metadata(epoch).keys())
        finally:
            probe.close()

    def close(self) -> None:
        self.manager.close()


def _arrays_only(state: TrainState) -> dict[str, Any]:
    out = {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
    }
    # Included ONLY when tracked, so EMA-off checkpoints keep their exact
    # historical tree. An --ema restore of a non-EMA checkpoint (or vice
    # versa) is an orbax tree mismatch — fail-loud, as the flag's help
    # documents. Omitting this line was a silent-drop bug: restore kept the
    # template's freshly-initialized EMA and eval served init-tinted
    # weights.
    if state.ema_params is not None:
        out["ema_params"] = state.ema_params
    return out
