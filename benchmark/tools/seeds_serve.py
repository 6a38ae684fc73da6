"""Read a served cell's compared numbers on many seeds in one process: a short
window at the cell's own load on each seed's weights, the served tokens against
the reference (the lower readings), and on the first ``--controls`` seeds the
control (the tokens the next lower precision puts first) and an altered token.
With ``--rates`` it sweeps the offered rate instead (one seed, each rate).

    python3 benchmark/tools/seeds_serve.py --workload W --seeds 1,2,... --seconds 25 --controls 3 --out FILE
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import schedule, stats  # noqa: E402
from benchmark.drivers import serve  # noqa: E402
from benchmark.run import log, open_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--lower", default="float8_e4m3fn")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    run = open_run(args.workload, seeds[0], args.seconds, False)
    session = serve.Session(run)
    lead, close = run.traffic["lead_seconds"], run.traffic["lead_seconds"] + args.seconds
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    first = True
    with out.open("a") as sink:
        for seed in seeds:
            for rate in rates:
                if not first:
                    session.engine = None
                    gc.collect()
                    session.engine = session.fresh_engine(seed)
                    session.engine.warmup()
                    session.warm_request(session.engine)
                first = False
                if rate is not None:
                    run.traffic["rate_per_s"] = rate
                requests = schedule.build(run.traffic, args.seconds)
                res = serve.serve(run, session.engine, requests, seed=seed, seconds=args.seconds, traced=False)
                m = stats.serve_metrics(res["records"], lead, close)
                queued = sum(rec["req"] is not None and rec["req"].state.value == "queued" for rec in res["records"])
                rec = {"seed": seed, "rate": run.traffic["rate_per_s"], "metrics": m, "drained_s": res["drained_s"],
                       "step_ms_p50": 1e3 * stats.quantile(res["step_s"], 0.5), "queued_at_end": queued}
                log(f"seed {seed} rate {run.traffic['rate_per_s']}: {m}; drained {res['drained_s']:.1f} s; step p50 {rec['step_ms_p50']:.1f} ms")
                if rate is None:
                    picked = serve.reference_sample(run, res["records"], requests, seed, close)
                    served = [
                        (schedule.prompt_ids(seed, i, requests[i]["prompt_len"], run.config["vocab_size"]), list(res["records"][i]["req"].generated))
                        for i in picked
                    ]
                    del res
                    session.engine = None
                    gc.collect()
                    rec["program"], rec["compared"] = serve.compare(run, served, seed)
                    log(f"  program: served_logit_gap {rec['program']:.5f} over {rec['compared']} tokens of {len(served)} requests")
                    if seeds.index(seed) < args.controls:
                        rec["control"], _ = serve.compare(run, served, seed, lower=args.lower)
                        rec["altered"], _ = serve.compare(run, served, seed, alter=True)
                        log(f"  control ({args.lower}) {rec['control']:.5f}; one token altered {rec['altered']:.5f}")
                sink.write(json.dumps(rec) + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
