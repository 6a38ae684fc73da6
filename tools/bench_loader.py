"""Input-pipeline micro-bench: disk-backed segmentation loader throughput.

Round-3 evidence for the loader concurrency work (`data/loader.py`): builds
a Carvana-style on-disk dataset (PNG image/mask pairs), then measures
`ShardedLoader` epoch throughput at several `num_workers` settings, plus the
in-memory synthetic path as the ceiling. The chip-side target is ~2,500+
img/s (ResNet-50 @224 per-chip rate, docs/PERF_ANALYSIS.md); whether disk
decode keeps up is a host-core question — this tool reports per-image decode
cost and thread-scaling so the per-host worker count can be sized
(the reference sizes the same knob with num_workers=15,
pytorch/resnet/main.py:100).

Usage: python tools/bench_loader.py [--n 256] [--hw 192] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def build_disk_dataset(root: Path, n: int, hw: int) -> None:
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    (root / "images").mkdir(parents=True, exist_ok=True)
    (root / "masks").mkdir(parents=True, exist_ok=True)
    for i in range(n):
        img = rng.integers(0, 256, (hw, hw, 3), dtype=np.uint8)
        mask = (rng.random((hw, hw)) > 0.5).astype(np.uint8) * 255
        Image.fromarray(img).save(root / "images" / f"ex{i:05d}.png")
        Image.fromarray(mask).save(root / "masks" / f"ex{i:05d}.png")


def bench_epochs(loader, epochs: int = 2) -> float:
    """img/s over full epochs (first epoch includes pool spin-up)."""
    n = 0
    t0 = time.perf_counter()
    for e in range(epochs):
        for batch in loader.epoch(e):
            n += batch["image"].shape[0]
    # Host-side loader bench: batches are device arrays already; count wall.
    return n / (time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--hw", type=int, default=192)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--platform", default="cpu", choices=("cpu", "tpu"))
    ap.add_argument("--workers", type=int, nargs="+", default=[0, 2, 4, 8])
    args = ap.parse_args()

    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform(args.platform)

    import os
    import tempfile

    from deeplearning_mpi_tpu.data.loader import ShardedLoader, prefetch
    from deeplearning_mpi_tpu.data.segmentation import SegmentationFolderDataset
    from deeplearning_mpi_tpu.runtime.mesh import create_mesh

    mesh = create_mesh()
    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        build_disk_dataset(root, args.n, args.hw)
        ds = SegmentationFolderDataset(root / "images", root / "masks", scale=1.0)

        # Raw per-image decode cost (single thread) — the scaling unit.
        t0 = time.perf_counter()
        for i in range(min(64, len(ds))):
            ds[i]
        per_image_ms = (time.perf_counter() - t0) / min(64, len(ds)) * 1e3

        results = {"n": args.n, "hw": args.hw, "batch": args.batch,
                   "host_cores": os.cpu_count(),
                   "decode_ms_per_image_1thread": round(per_image_ms, 2),
                   "img_per_s": {}}
        for w in args.workers:
            loader = ShardedLoader(
                ds, args.batch, mesh, shuffle=True, num_workers=w
            )
            rate = bench_epochs(loader)
            results["img_per_s"][f"workers_{w}"] = round(rate, 1)

        # Prefetch-wrapped (the trainer's consumption pattern).
        loader = ShardedLoader(ds, args.batch, mesh, shuffle=True)
        n = 0
        t0 = time.perf_counter()
        for e in range(2):
            for batch in prefetch(loader.epoch(e)):
                n += batch["image"].shape[0]
        results["img_per_s"]["default_with_prefetch"] = round(
            n / (time.perf_counter() - t0), 1
        )
        # Projection: decode parallelism scales with cores until the chip
        # rate (docs/PERF_ANALYSIS.md: ~2,576 img/s @224) is covered.
        results["cores_needed_for_2500_img_s"] = round(
            2500 * per_image_ms / 1e3, 1
        )
        print(json.dumps(results))


if __name__ == "__main__":
    main()
