"""The chip benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]
