"""Tests of the benchmark itself. Not collected by the repo's tier-1 run
(that collects ``tests/``); run by hand, off the chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
