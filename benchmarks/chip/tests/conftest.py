"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

(tier-1's ``testpaths = tests`` does not collect them)."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
