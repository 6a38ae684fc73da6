"""Seeded DMT003: the serving engine's ``fetch`` (a wait and a copy back)
inside a marked hot loop with no audit."""
from deeplearning_mpi_tpu.serving.launch import fetch


def decode_loop(fn, kv, tokens):  # dmt-lint: hot-loop
    val = None
    for tok in tokens:
        kv, out = fn(kv, tok)
        val = fetch(out)  # seeded: DMT003 — per-step device fetch
    return val
