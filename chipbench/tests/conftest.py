"""Tests of the benchmark's own yardstick. Run from the root of the repo:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``."""

import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
