"""Tests of the chip benchmark's own code, on the CPU at small sizes:

  JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
