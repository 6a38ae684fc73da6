"""The readings a session cell's limits are set from, on the chip:

    python3 benchmark/keye/tools/faults.py --workload keye-serve-long --seed <n> --seconds 51 \
        [--sessions float8_e4m3fn,half_topk] [--probe all]

One whole run of the cell as ``benchmark/run.py`` makes it (set-up, the lead,
the window, the probe after the close, the reference: its end-to-end metric
and its checks are printed first, a sound run's readings). Then, against the
same reference logits, the tokens the reference itself puts first at the same
positions when its matmul operands are rounded to a lower precision (the
control) or a fault is planted (``reference.FAULTS``): each such way is held
to the cell's limits as the served tokens were, and has to come out not
correct. ``--sessions`` names the ways judged on the two long sessions (a pass
over 57k positions each: name few), ``--probe`` those judged on the short
probe; ``all`` is both precisions and every fault, an empty string none.
``--first_sessions N`` serves only the first N sessions: a cheap sound reading
of the probe and of the pooled keys on one more seed. The one fault only the
program can have, indexer keys never written to the pool, reads 1.0 under
``index_key_gap`` by construction (a zero row against a key of unit scale;
``benchmark/tests/test_sessions.py`` plants it).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmark import run as harness  # noqa: E402
from benchmark.drivers import serve_sessions as driver  # noqa: E402
from benchmark.run import log  # noqa: E402

PRECISIONS = ("bfloat16", "float8_e4m3fn")


def ways(arg: str, reference: Any) -> list[str]:
    every = [*PRECISIONS, *getattr(reference, "FAULTS", ())]
    names = every if arg == "all" else [n for n in arg.split(",") if n]
    unknown = [n for n in names if n not in every]
    if unknown:
        raise SystemExit(f"no such way: {unknown}; there are {every}")
    return names


def verdict(run: harness.Run, name: str, got: dict[str, float]) -> dict[str, Any]:
    """``got`` against each limit the cell states under ``name``."""
    held = {name + s: (got[stat], run.limits[name + s]) for s, stat in driver.STATS.items() if name + s in run.limits}
    return {**got, "limits": {n: lim for n, (_, lim) in held.items()}, "correct": all(v <= lim for v, lim in held.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--sessions", default="float8_e4m3fn")
    ap.add_argument("--probe", default="all")
    ap.add_argument("--first_sessions", type=int, default=0, help="serve only the first N sessions (a cheap sound reading of the probe and the pooled keys)")
    args = ap.parse_args()
    run = harness.open_run(args.workload, args.seed, args.seconds, False)
    if run is None:
        return 2
    if args.first_sessions:
        run.traffic["prompt_tokens"] = run.traffic["prompt_tokens"][: args.first_sessions]
    reference = driver.modules(run.config)[2]
    taken = driver.measure(run)
    clean = driver.judge(run, taken)
    log(run.setup.line())
    print(json.dumps({"sound": {
        "seed": args.seed, "correct": run.correct, "serve_itl_p95_ms": run.end_to_end.get("serve_itl_p95_ms"), "setup_s": run.setup.total,
        "memory_peak_bytes": run.memory_peak_bytes, "check": {n: {"value": v, "limit": lim, "ok": bool(v <= lim)} for n, v, lim in run.checks},
    }}), flush=True)

    served = taken["served"]
    probe = [taken["probe"]] if taken["probe"] else []
    params = driver.seed_params(run, args.seed)
    for what, name, pairs, base, chosen in (
        ("sessions", "served_logit_gap", served, clean[: len(served)], args.sessions),
        ("probe", "probe_logit_gap", probe, clean[len(served):], args.probe),
    ):
        if not pairs or len(base) != len(pairs):
            continue
        for way in ways(chosen, reference):
            how = {"lower": way} if way in PRECISIONS else {"faults": frozenset([way])}
            theirs = [lg.argmax(axis=-1) for lg in driver.reference_logits(run, params, pairs, **how)]
            got = driver.gap_stats(base, theirs, f"{what}, the reference's own choices under {way}")
            print(json.dumps({what: way, "seed": args.seed, **verdict(run, name, got)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
