"""Run with ``JAX_PLATFORMS=cpu python -m pytest hsbench/tests -q`` from the
root of the checkout. These tests are outside ``tests/`` on purpose: the
tier-1 count of the program is not theirs to change."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
