import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for path in (str(ROOT / "src"), str(ROOT)):
    if path not in sys.path:
        sys.path.insert(0, path)

# the benchmark's tests run on the CPU: rehearsals, the control at tiny
# sizes, and the trace reduction on a recorded file
os.environ.setdefault("JAX_PLATFORMS", "cpu")
