"""The decode step's latent attention alone, on the chip: the Pallas kernel
that walks each row's live pages (``ops/pallas/latent_decode.py``, through
``ops.latent_attention.paged_absorbed_attention``) against the absorbed form
in XLA over the table's gathered rectangle (``absorbed_attention``, what the
decode step ran before the kernel), at ``kimi-serve-long``'s widths: 64
heads, a latent of 512 + 64, a pool of 5 layers x 4,801 blocks of 128, a
table of 576 blocks a row, scrambled block ids and the rows' lengths drawn
from the cell's range. Prints one JSON line: each form's time a call (one
layer), the kernel's roofline share (every live latent row once, the
absorbed core's operations; the chip's peaks from ``benchmark/peaks.json``)
and the largest gap between the two outputs.

Usage: python tools/bench_latent_decode.py [--rows 16] [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _clock(fn, args, steps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=4801, help="pool blocks a layer (fewer: a smoke run)")
    ap.add_argument("--width", type=int, default=576, help="table blocks a row")
    ap.add_argument("--dtype", default="bfloat16", help="float32: a smoke run on a CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import costs as peaks
    from deeplearning_mpi_tpu.ops.latent_attention import absorbed_attention, paged_absorbed_attention

    layers, blocks, width = 5, args.blocks, args.width
    bs, heads, kvr, rope, nope, dv = 128, 64, 512, 64, 128, 128
    rows, layer, scale, dtype = args.rows, 3, 0.1446797, jnp.dtype(args.dtype)
    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(min(4096, width * bs // 2), width * bs - width * bs // 10, rows)
    lengths[0] = width * bs - width * bs // 10  # the longest row: the cell's 66,333 of 73,728 at the close
    tables = np.zeros((rows, width), np.int32)
    ids = rng.permutation(np.arange(1, blocks))
    at = 0
    for r, n in enumerate(lengths):
        used = -(-int(n) // bs)
        tables[r, :used] = np.resize(ids[at:], used)
        at = (at + used) % (blocks - 1)
    keys = jax.random.split(jax.random.key(args.seed), 5)
    c_pool = jax.random.normal(keys[0], (layers, blocks, bs, kvr), dtype)
    kpe_pool = jax.random.normal(keys[1], (layers, blocks, rope, bs), dtype)
    q_nope = jax.random.normal(keys[2], (rows, 1, heads, nope), dtype)
    q_pe = jax.random.normal(keys[3], (rows, 1, heads, rope), dtype)
    w_kvb = (jax.random.normal(keys[4], (kvr, heads, nope + dv), jnp.float32) / kvr**0.5).astype(dtype)
    last = jnp.asarray(lengths - 1, jnp.int32)
    tables_d = jnp.asarray(tables)

    kernel = jax.jit(lambda q, p, c, k, t, n, w: paged_absorbed_attention(q, p, c, k, layer, t, n, w, scale=scale))

    def gathered(q, p, c, k, t, n, w):
        c_seq = c[layer, t].reshape(rows, width * bs, kvr)
        k_seq = jnp.swapaxes(k[layer, t], -1, -2).reshape(rows, width * bs, rope)
        valid = (jnp.arange(width * bs)[None, :] <= n[:, None])[:, None]
        return absorbed_attention(q, p, c_seq, k_seq, w, scale=scale, valid=valid)

    xla = jax.jit(gathered)
    operands = (q_nope, q_pe, c_pool, kpe_pool, tables_d, last, w_kvb)
    got, want = (np.asarray(f(*operands), np.float32) for f in (kernel, xla))
    kernel_s, xla_s = _clock(kernel, operands, args.steps), _clock(xla, operands, args.steps)
    live = int(lengths.sum())
    flops = 2 * heads * (2 * kvr + rope) * live
    nbytes = live * (kvr + rope) * 2
    kind = jax.devices()[0].device_kind
    timed = kind in peaks.PEAKS  # a chip's times; a CPU run checks the numbers alone
    print(json.dumps({
        "device_kind": kind, "rows": rows, "live_positions": live,
        "kernel_ms": round(1e3 * kernel_s, 4) if timed else None,
        "xla_ms": round(1e3 * xla_s, 4) if timed else None,
        "kernel_roofline_pct": round(100 * peaks.roofline_seconds(flops, nbytes, kind) / kernel_s, 2) if timed else None,
        "max_abs_gap": float(np.abs(got - want).max()), "max_abs_out": float(np.abs(want).max()),
        "mean_abs_gap": float(np.abs(got - want).mean()),
    }))


if __name__ == "__main__":
    main()
