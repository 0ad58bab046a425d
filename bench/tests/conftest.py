"""The benchmark's own tests run on the CPU, from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
