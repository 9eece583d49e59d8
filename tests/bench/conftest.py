"""The benchmark's own tests: they import the harness's modules the way
``bench/run.py`` does, with ``bench/`` on the path (``bench.py`` at the root
shadows ``bench`` as a package name)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
