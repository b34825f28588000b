import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU and write no compilation cache
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH / "lib", BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
