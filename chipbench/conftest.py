import os
import sys
from pathlib import Path

# the benchmark's own tests run on the CPU, with the program from src/
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
