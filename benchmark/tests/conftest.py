"""The benchmark's own tests run on the CPU; they never take a chip.
Run them with

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
