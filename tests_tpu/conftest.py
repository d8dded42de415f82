"""On-TPU test tier: the real Pallas kernels compiled by Mosaic on hardware —
NOT the interpreter-mode runs in tests/.

Run on a machine that holds a chip (from the sandbox: through the chip tool):

    python -m pytest tests_tpu/ -q

One process: pytest itself initialises the TPU backend and holds the chip.
No chip is a failure, never a skip — an exit code 0 from a machine that ran
nothing would read as "the kernels pass".
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: requires a real TPU chip (compiled Mosaic kernels)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        item.add_marker(pytest.mark.tpu)


def pytest_sessionstart(session):
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]     # a backend that cannot start raises here
    if dev.platform != "tpu":
        pytest.exit(f"tests_tpu/ needs a TPU chip; JAX found "
                    f"{dev.platform!r} ({dev.device_kind}). Kernel coverage "
                    f"without a chip is the interpreter-mode tier in tests/.",
                    returncode=1)
    enable_compile_cache()
    print(f"tests_tpu: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
