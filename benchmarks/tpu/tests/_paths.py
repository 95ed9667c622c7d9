"""Puts the benchmark directory and the program's ``src`` on the path."""
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(os.path.dirname(BENCH))
FIXTURES = os.path.join(TESTS, "fixtures")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
