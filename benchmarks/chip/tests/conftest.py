"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/chip/tests -q``. Seconds, no chip; not under ``tests/``, so the
tier-1 count and time do not move."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HOME = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HOME))
