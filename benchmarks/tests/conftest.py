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


def served_of(mix):
    """The ``served`` arguments of the configurations whose cells, in the
    repo's BENCHMARK.json, send the traffic mix ``mix``."""
    from benchmarks import run as R

    m = R.load_manifest(ROOT)
    files = {c["name"]: c["file"] for c in m["configs"]}
    return [R.load_json(ROOT, files[w["config"]])["served"]
            for w in m["workloads"] if w["traffic"] == mix]
