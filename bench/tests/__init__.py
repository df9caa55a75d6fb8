"""Tests of the benchmark itself, on the CPU: the program is imported from
the checkout's ``src`` whether or not ``PYTHONPATH`` names it."""
import sys
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
