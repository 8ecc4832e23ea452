"""The benchmark's own tests run on the CPU at toy sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 suite (``tests/``).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.makedirs(os.path.join(ROOT, "benchmark", ".scratch"), exist_ok=True)
