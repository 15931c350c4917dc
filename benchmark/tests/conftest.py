"""The benchmark's own tests. They are not under `tests/`, so the
repo's tier-1 command does not collect them; run them by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
