"""The benchmark's own tests: run by hand, on the CPU,
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
