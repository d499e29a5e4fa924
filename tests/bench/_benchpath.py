"""Puts the benchmark's own directory on the import path of its tests."""
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
