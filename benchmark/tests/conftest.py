"""CPU tests of the benchmark's own arithmetic and of its comparison.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repository's tier-1 run (that collects ``tests/`` only).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the program
