import os
import sys
from pathlib import Path

# The benchmark's tests run on the CPU at small sizes; the system under test
# is imported from src like the benchmark itself does.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
