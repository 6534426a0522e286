"""The benchmark's own tests run on the CPU at a tiny size; they are
not part of the repository's test suite (``pytest bench/tests``)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
