"""The benchmark's own tests run on the CPU, at toy size.

``JAX_PLATFORMS`` is set before JAX starts, as ``tests/conftest.py`` sets it;
run with it or without it, these tests never ask for a chip.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
