"""Tests of the benchmark's own code: ``python -m pytest benchmark/tests``.

They run on the CPU (rehearsals at SF 0.01 under ``BENCH_ALLOW_CPU=1``)
and are not part of the program's tier-1 suite under ``tests/``.
"""

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
