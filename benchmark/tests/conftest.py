"""Tests of the benchmark's own code, on the CPU at toy size. Run by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider

They are outside the repo's tier-1 tests (``tests/``) and are never run
together with them in one process: both pin the CPU backend's device count.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
