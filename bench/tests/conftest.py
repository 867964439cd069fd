"""Tests of the benchmark itself, at CPU speed: ``pytest bench/tests``
from the root of the checkout. They put the checkout and its ``src`` on
the path, keep JAX on the CPU unless told otherwise, and keep the
persistent compilation cache off."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
