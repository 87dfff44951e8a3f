"""Tests of the benchmark's own code.  They run on the CPU at small sizes:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
