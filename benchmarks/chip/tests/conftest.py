"""The benchmark's own tests run on the CPU, from the repository root:

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]
