"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

They never look for a chip: JAX is held to the CPU, and the tests that
drive a run stub the harness's look for one.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
