"""The readings ``kimi-serve-long``'s limits are set from, on the chip:

    python3 benchmark/kimi/tools/faults.py --workload kimi-serve-long --seed <n> --seconds 51 \
        [--sessions float8_e4m3fn,no_mscale]

One whole run of the cell as ``benchmark/run.py`` makes it, then, against the
same reference logits, the tokens the reference itself puts first at the same
positions when its matmul operands are rounded to a lower precision (the
control) or a fault is planted (``benchmark/kimi/reference.py:FAULTS``: the
softmax scale without YaRN's ``mscale^2``, the router's bias in the weights,
the routed scale or the shared expert left out, the latent not normed, the
shared key part not rotated): each such way is held to the cell's limits as
the served tokens were, and has to come out not correct. It is
``benchmark/keye/tools/faults.py`` (the tool reads the configuration's own
reference and its ``FAULTS``); every way is judged unless ``--sessions`` names
some, and there is no probe.
"""

from __future__ import annotations

import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmark.keye.tools.faults import main  # noqa: E402

if __name__ == "__main__":
    if "--sessions" not in sys.argv:
        sys.argv += ["--sessions", "all"]
    sys.exit(main())
