"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
Tier-1 collects ``tests/`` only; nothing here is part of it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
