"""The benchmark's own tests (``python -m pytest benchmark/tests -q``). They
run on the CPU and live with the benchmark; they are not part of tier-1."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
