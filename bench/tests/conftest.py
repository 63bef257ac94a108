"""The benchmark's own tests (run by hand: ``python -m pytest bench/tests``;
the repository's test run collects only ``tests/``)."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
