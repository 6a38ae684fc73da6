"""Spread of every metric in the sets a ``sets.py`` file holds: for each set
the median and the distance between the quartiles as a share of the median
(``statistics.quantiles(values, n=4)``, as the driver's check takes it).

    python3 benchmark/tools/spreads.py chiprun_out/sets_serve.jsonl
"""

from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.stats import spread  # noqa: E402


def main() -> int:
    sets: dict[tuple[str, str], list[float]] = defaultdict(list)
    wrong = 0
    for line in Path(sys.argv[1]).read_text().splitlines():
        rec = json.loads(line)
        if rec["line"] is None or not rec["line"]["correct"]:
            wrong += 1
            continue
        for name, m in rec["line"]["metrics"].items():
            sets[(name, rec["set"])].append(m["value"])
    for (name, which), values in sorted(sets.items()):
        s = spread(values) if len(values) >= 2 else float("nan")
        print(f"{name:32s} set {which}: n {len(values)}, median {statistics.median(values):.6g}, spread {100 * s:.3f}%, "
              f"min {min(values):.6g}, max {max(values):.6g}")
    print(f"runs not correct or without a result: {wrong}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
