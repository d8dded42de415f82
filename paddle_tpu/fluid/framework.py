"""fluid.framework — legacy framework module (ref python/paddle/fluid/framework.py:
Program/Block/Variable/Parameter classes, dygraph-mode switches, set_flags:7629).
Aliases onto paddle_tpu.static's recorded-Program IR and the eager core."""
from __future__ import annotations

from ..compat import CPUPlace, CUDAPlace  # noqa: F401
from ..framework.core import Parameter, Tensor  # noqa: F401
from ..framework.flags import get_flags, set_flags  # noqa: F401
from ..framework.random import seed as _seed
from ..static.graph import (Block, Operator, Program, Variable,  # noqa: F401
                            default_main_program, default_startup_program,
                            in_static_mode, program_guard)

EagerParamBase = Parameter


def in_dygraph_mode() -> bool:
    """ref fluid/framework.py in_dygraph_mode — true unless paddle.enable_static."""
    return not in_static_mode()


_non_static_mode = in_dygraph_mode
_in_legacy_dygraph = in_dygraph_mode


def _current_expected_place():
    import jax

    d = jax.devices()[0]
    return CUDAPlace(0) if d.platform in ("tpu", "gpu") else CPUPlace()


def is_compiled_with_cuda() -> bool:
    from ..device import is_compiled_with_cuda as f

    return f()


def cuda_places(device_ids=None):
    import jax

    n = len([d for d in jax.devices() if d.platform != "cpu"]) or 1
    ids = device_ids if device_ids is not None else range(n)
    return [CUDAPlace(i) for i in ids]


def cpu_places(device_count=1):
    return [CPUPlace() for _ in range(device_count)]


def device_guard(device=None):
    import contextlib

    return contextlib.nullcontext()


def set_random_seed(s):
    _seed(s)


class dygraph_only:  # decorator used by legacy code
    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *a, **k):
        return self._fn(*a, **k)
