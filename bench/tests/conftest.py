import os
import sys
from pathlib import Path

# the harness's CPU tests run on the CPU; the chip-only paths exit early
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
