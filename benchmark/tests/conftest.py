"""CPU self-tests of the benchmark: tiny shapes, nothing timed. Four virtual
CPU devices, so that the four-chip cell's path runs here too."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
