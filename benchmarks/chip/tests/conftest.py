"""CPU tests of the chip benchmark, run by path:

  python -m pytest benchmarks/chip/tests

They sit outside the repository's ``testpaths``.  Every Pallas kernel runs
interpreted here, and no number they print is a device measurement.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
