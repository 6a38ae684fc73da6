"""Run the benchmark's own command several times, one new process each, and
keep every result line: the sets of runs the bounds are set from.

    python3 benchmark/tools/sets.py --workload W --seeds 1,2,3 --sets A,B [--trace 0] [--seconds N] --out FILE

This parent never touches jax. Each line of FILE: set, seed, exit code, the
seconds the whole process took, the result line.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", default="A")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds if args.seconds is not None else doc["run_seconds"]
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    worst = 0
    with out.open("a") as sink:
        for name in args.sets.split(","):
            for seed in args.seeds.split(","):
                cmd = [*doc["command"], "--workload", args.workload, "--seed", seed, "--seconds", f"{seconds:g}", "--trace", str(args.trace)]
                t0 = time.monotonic()
                proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
                took = time.monotonic() - t0
                lines = proc.stdout.strip().splitlines()
                try:
                    line = json.loads(lines[-1]) if lines else None
                except json.JSONDecodeError:
                    line = None
                sink.write(json.dumps({"set": name, "seed": int(seed), "trace": args.trace, "rc": proc.returncode, "took_s": took, "line": line}) + "\n")
                sink.flush()
                tail = proc.stderr.strip().splitlines()
                keep = [t for t in tail if t.startswith(("window", "setup_s", "after the window", "correct", "reference", "worst leaf"))]
                print(f"[{name} seed {seed}] rc {proc.returncode}, {took:.1f} s", *keep, sep="\n  ", flush=True)
                if proc.returncode or line is None or not line.get("correct"):
                    print("\n".join(tail[-25:]), flush=True)
                    worst = 1
    return worst


if __name__ == "__main__":
    sys.exit(main())
