"""The benchmark's own tests run on the CPU: ``python -m pytest
benchmarks/tests``. They are not part of the repository's tier-1 count."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
