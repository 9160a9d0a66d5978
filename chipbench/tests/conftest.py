import os
import sys
from pathlib import Path

# the benchmark's tests run on the host CPU; the harness itself refuses it
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (BENCH_DIR.parent / "src", BENCH_DIR):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
