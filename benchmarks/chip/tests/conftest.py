"""CPU tests of the chip benchmark: tiny widths, Pallas interpret mode.

Run from the repository root:

    python -m pytest benchmarks/chip/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parents[2] / "src"))
