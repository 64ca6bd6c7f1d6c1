import os
import sys
import tempfile
from pathlib import Path

# the benchmark's tests run on the CPU at a tiny size; their compile cache
# lives in a directory of their own
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-cache-"))

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
