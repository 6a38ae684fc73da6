"""Read a training cell's compared numbers on many seeds in one process: the
program's first steps against the reference (the lower readings), and on the
first ``--controls`` seeds the control (the reference in the next lower
precision) and each fault, planted in the reference put in the program's place
(the upper readings). No measured window: these numbers need none.

    python3 benchmark/tools/seeds_train.py --workload W --seeds 1,2,... --controls 3 --out FILE
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import check, reference  # noqa: E402
from benchmark.drivers import train  # noqa: E402
from benchmark.run import log, open_run  # noqa: E402


def fault_weights(name: str, rows: int, seq: int) -> np.ndarray:
    w = np.ones((rows, seq - 1), np.float32)
    if name == "half_batch":  # half of the batch left out, the mean taken over the rest
        flat = w.reshape(-1)
        flat[flat.size // 2:] = 0.0
    elif name == "no_exchange":  # every chip steps on its own rows' gradient: chip 0's view
        w[1:] = 0.0
    return w


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--lower", default="float8_e4m3fn", help="the control's precision; 'none' leaves the control out")
    ap.add_argument("--faults", default="", help="comma list; default: every fault the cell can have")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    run = open_run(args.workload, seeds[0], 0.0, False)
    session = train.Session(run)
    sample = next(session.feed.loader.epoch(10**6))
    session.trainer.warmup(sample)
    n = run.traffic["first_steps"]
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    faults = args.faults.split(",") if args.faults else ["half_batch", "unchanged"] + (["no_exchange"] if run.chips > 1 else [])
    with out.open("a") as sink:
        for k, seed in enumerate(seeds):
            if k:
                session.reseed(seed)
            t0 = time.monotonic()
            program = session.first_steps(seed, n)
            session.free()
            t1 = time.monotonic()
            ref = reference.follow_training(run.config, seed, program["batches"], devices=run.devices)
            t2 = time.monotonic()
            gaps, note = check.training(program, ref)
            rec = {"seed": seed, "program": gaps, "loss": program["loss"], "ref_loss": ref["loss"], "program_s": t1 - t0, "reference_s": t2 - t1}
            log(f"seed {seed}: program {gaps}\n  {note}; program {t1 - t0:.1f} s, reference {t2 - t1:.1f} s")
            if k < args.controls:
                if args.lower != "none":
                    ctl = reference.follow_training(run.config, seed, program["batches"], lower=args.lower, devices=run.devices)
                    rec["control"], note = check.training(ctl, ref)
                    log(f"  control ({args.lower}): {rec['control']}\n  {note}")
                for fault in faults:
                    w = [fault_weights(fault, *b.shape) for b in program["batches"]]
                    # a step that returns its state unchanged: the same reference with a step size of nought
                    cfg = run.config if fault != "unchanged" else {**run.config, "train": {**run.config["train"], "learning_rate": 0.0}}
                    bad = reference.follow_training(cfg, seed, program["batches"], weights=w, devices=run.devices)
                    rec[fault], note = check.training(bad, ref)
                    log(f"  fault {fault}: {rec[fault]}\n  {note}")
            sink.write(json.dumps(rec) + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
