"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not part of the repository's test suite (``pytest.ini`` collects
``tests/`` only)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
