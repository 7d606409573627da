"""Self-tests of the yardstick (not tier-1): ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests -q -p no:cacheprovider`` from the repo's root."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
