"""The benchmark's own tests run on the CPU (never collected by the
repository's ``pytest tests/``):  ``python3 -m pytest benchmark/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
