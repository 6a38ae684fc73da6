"""Convert a reference PyTorch ``.pth`` checkpoint into a framework
checkpoint that ``--resume`` / ``--eval_only`` restore.

The reference leaves migrating users with raw DDP state_dicts —
``torch.save(ddp_model.state_dict(), path)`` (``pytorch/resnet/main.py:139``,
``pytorch/unet/train.py:216``). This entry point reads one, converts the
layout (``utils/torch_import``), wraps it in a full train state (fresh
optimizer, step 0 — the reference never saved optimizer state to begin
with), and writes an Orbax checkpoint under ``--model_dir/--model_filename``:

    dmt-import-torch --arch unet --input unet_distributed.pth
    dmt-train-unet --resume --reference_topology ...   # continues from it

    dmt-import-torch --arch resnet18 --input resnet_distributed.pth
    dmt-train-resnet --resume --torch_padding ...      # ditto

UNet checkpoints restore into ``UNet(reference_topology=True)`` — the
reference's decoder keeps channels through the upsample (``pytorch/unet/
model.py:37-38``), a different param-shape contract than our default — so
the train/eval run must pass ``--reference_topology`` too.

The fresh optimizer state is written with the trainers' DEFAULT optimizer
shape (constant LR, bare-float hyperparams). Resuming with ``--lr_schedule
cosine`` changes the optax state tree and will fail to restore — true of
any checkpoint whose run flags disagree, not just imported ones.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--input", required=True, help="path to the .pth file")
    parser.add_argument("--arch", required=True,
                        choices=["unet", "resnet18", "resnet34", "resnet50",
                                 "resnet101", "resnet152"])
    parser.add_argument("--model_dir", default="saved_models")
    parser.add_argument("--model_filename", default=None,
                        help="checkpoint name (default: the matching "
                        "trainer's default, so --resume finds it)")
    parser.add_argument("--epoch", type=int, default=0,
                        help="epoch label for the checkpoint (the .pth "
                        "carries none; resume continues after this)")
    parser.add_argument("--num_classes", type=int, default=10,
                        help="resnet head width (reference: 10, main.py:41)")
    parser.add_argument("--out_classes", type=int, default=1,
                        help="unet head channels (reference default 2, "
                        "run.sh trains 1)")
    parser.add_argument("--bilinear", action="store_true",
                        help="the .pth came from up_sample_mode='bilinear'")
    parser.add_argument("--platform", default=None, choices=("cpu", "tpu"))
    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform(args.platform)

    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.train import Checkpointer, create_train_state
    from deeplearning_mpi_tpu.train.trainer import build_optimizer
    from deeplearning_mpi_tpu.utils import torch_import

    state_dict = torch_import.load_pth(args.input)

    # Optimizer hyperparameters come from the matching trainer's OWN parser
    # defaults — the optax state tree written here must equal the restore
    # template the trainer builds, and a hardcoded copy would silently
    # drift if a trainer default ever changes.
    if args.arch == "unet":
        from deeplearning_mpi_tpu.cli import train_unet
        from deeplearning_mpi_tpu.models import UNet

        variables = torch_import.convert_reference_unet(state_dict)
        model = UNet(
            out_classes=args.out_classes, bilinear=args.bilinear,
            reference_topology=True,
        )
        sample = jnp.zeros((1, 64, 64, 3))
        d = train_unet.build_parser().parse_args([])
        tx = build_optimizer(d.optimizer, d.learning_rate, clip_norm=d.clip_norm)
        default_name = d.model_filename
    else:
        from deeplearning_mpi_tpu.cli import train_resnet
        from deeplearning_mpi_tpu.models import get_model

        variables = torch_import.convert_torchvision_resnet(
            state_dict, args.arch
        )
        model = get_model(
            args.arch, num_classes=args.num_classes, stem="imagenet",
            torch_padding=True,
        )
        sample = jnp.zeros((1, 32, 32, 3))
        d = train_resnet.build_parser().parse_args([])
        tx = build_optimizer(
            d.optimizer, d.learning_rate, momentum=d.momentum,
            weight_decay=d.weight_decay,
        )
        default_name = d.model_filename

    template = create_train_state(
        model, jax.random.key(0), sample, tx
    )
    imported_params = jax.tree.map(jnp.asarray, variables["params"])
    imported_stats = jax.tree.map(jnp.asarray, variables["batch_stats"])

    # Shapes, not just structure: a head-width mismatch (e.g. a .pth
    # trained at the reference's default out_classes=2 imported without
    # --out_classes 2) has an identical tree structure and would otherwise
    # surface as an opaque orbax error at restore time.
    def flat_shapes(tree):
        return {
            "/".join(str(getattr(k, "key", k)) for k in path): tuple(
                int(d) for d in getattr(v, "shape", ())
            )
            for path, v in jax.tree_util.tree_leaves_with_path(tree)
        }

    want = flat_shapes(template.params)
    got = flat_shapes(imported_params)
    if want != got:
        diffs = sorted(
            {k for k in want.keys() | got.keys() if want.get(k) != got.get(k)}
        )
        raise SystemExit(
            f"imported param shapes do not match a fresh {args.arch} init —\n"
            f"model flags (--out_classes/--num_classes/--bilinear) probably "
            f"disagree with how the .pth was trained.\n"
            f"mismatched leaves: {diffs[:8]}"
        )

    state = template.replace(
        params=imported_params,
        batch_stats=imported_stats,
        opt_state=tx.init(imported_params),
    )

    name = args.model_filename or default_name
    checkpointer = Checkpointer(f"{args.model_dir}/{name}")
    try:
        checkpointer.save(state, epoch=args.epoch)
        checkpointer.manager.wait_until_finished()
    finally:
        checkpointer.close()
    n_params = sum(x.size for x in jax.tree.leaves(imported_params))
    print(
        f"imported {args.arch} ({n_params:,} params) from {args.input} -> "
        f"{args.model_dir}/{name} @ epoch {args.epoch}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
