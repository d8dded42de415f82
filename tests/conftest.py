"""Test config: virtual 8-device CPU mesh (SURVEY §4 test plan — the analogue
of the reference's multi-process subprocess trick, cheaper + deterministic)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# pin the platform in-process as well as through the env var
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np
import pytest

# Slow shards (full-model e2e training, big op sweeps, heavy recipes): the
# quick tier (`pytest -m quick`) excludes these and finishes in ~2 min —
# the CI-able default; the full suite is the pre-merge gate (README).
_SLOW_FILES = {
    "test_vision.py", "test_sparse.py", "test_models_e2e.py", "test_ocr.py",
    "test_fused_transformer.py", "test_fleet_static_incubate.py",
    "test_op_sweep.py", "test_dy2static.py", "test_distributed.py",
    "test_engine_parity.py", "test_misc_api.py", "test_subsystems.py",
    "test_ring_flash_attention.py", "test_flash_attention.py",
    "test_generate.py", "test_int8_decode.py", "test_fused_ce.py",
    "test_static_amp_shims.py", "test_tcp_store.py",
    "test_distributed_extras.py", "test_extensions.py",
    "test_auto_parallel_partition.py", "test_fleet_executor.py",
    "test_multiprocess_train.py", "test_moe_llama.py",
    "test_serving.py", "test_op_sweep_extended.py", "test_sequence_ops.py",
    "test_functional_sweep.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.path.name in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield
