"""benchmark/tests run on their own (``python -m pytest benchmark/tests``),
on the CPU, at sizes a test run can hold.  They are not part of tier-1."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
