"""CPU tests of the benchmark's own code: the reduction from traces and
counters to metrics, the copied generator and reference, finding pieces by
name, and a whole run at a tiny size with the served path broken."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
