"""Tests of the benchmark itself. Run by the builder, not by tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
