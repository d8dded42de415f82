"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit.

Top-level namespace mirrors `paddle` (ref: python/paddle/__init__.py): tensor
ops are flat functions here, `nn`/`optimizer`/`distributed`/... are
subpackages. Everything executes eagerly op-by-op (dygraph parity) and traces
into a single XLA program under `paddle_tpu.jit.to_static`.
"""
from __future__ import annotations

from . import version  # noqa: F401

__version__ = version.full_version

# On CPU (tests / local dev) match the reference's numerics: true-f32 matmuls
# and 64-bit int/float dtypes. On TPU keep JAX performance defaults (bf16
# MXU passes) — models run bf16 there anyway.
import os as _os

if _os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    import jax as _jax

    # Pin the config knob too, so every child process that imports
    # paddle_tpu with the variable set (launch trainers, store clients,
    # data-loader workers, test scripts) stays off the parent's chip.
    _jax.config.update("jax_platforms", "cpu")
    _jax.config.update("jax_enable_x64", True)
    _jax.config.update("jax_default_matmul_precision", "highest")

# framework core
from .framework import (Tensor, Parameter, EagerParamBase, no_grad, enable_grad,
                        is_grad_enabled, set_default_dtype, get_default_dtype, set_flags,
                        get_flags, seed, get_rng_state, set_rng_state)
from .framework.dtype import (bfloat16, bool_ as bool, complex64, complex128, float16, float32,
                              float64, int8, int16, int32, int64, uint8)

# the whole tensor-op surface re-exported flat (paddle.<op> style)
from .tensor import *  # noqa: F401,F403
from .tensor import (abs, add, matmul, mean, ones, zeros, to_tensor, concat, reshape,
                     transpose)  # explicit for linters

# subpackages
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distributed  # noqa: F401
from . import framework  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import linalg  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import static  # noqa: F401
from . import tensor  # noqa: F401
from . import utils  # noqa: F401
from . import vision  # noqa: F401
from . import incubate  # noqa: F401
from . import sparse  # noqa: F401
from . import fft  # noqa: F401
from . import distribution  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import inference  # noqa: F401
from . import autotune  # noqa: F401
from . import quantization  # noqa: F401
from . import signal  # noqa: F401
from . import text  # noqa: F401
from . import regularizer  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from .batch import batch  # noqa: F401
from .regularizer import L1Decay, L2Decay  # noqa: F401


from . import sysconfig  # noqa: F401
from . import onnx  # noqa: F401

from .framework.io_state import save, load  # paddle.save/paddle.load

# device helpers (paddle.set_device / get_device)
from .device import get_device, set_device, is_compiled_with_cuda, is_compiled_with_xpu

# hapi Model at top level (paddle.Model)
from .hapi import Model  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .hapi import summary  # noqa: F401
from . import hub  # noqa: F401
from .cost_model import flops  # noqa: F401
from .compat import (CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, IPUPlace,
                     LazyGuard, MLUPlace, NPUPlace, TPUPlace, XPUPlace,
                     add_n, batch, cast, check_shape, create_parameter, diagonal,
                     disable_signal_handler, dsplit, dtype, finfo, frexp,
                     get_cuda_rng_state, hsplit, iinfo, index_add_, is_complex,
                     is_floating_point, is_integer, logcumsumexp, mv, reverse,
                     set_cuda_rng_state, set_grad_enabled, set_printoptions, sgn,
                     squeeze_, tanh_, tolist, unsqueeze_, vsplit)
from .distributed.parallel import DataParallel  # noqa: F401
from .framework.param_attr import ParamAttr  # noqa: F401


def is_compiled_with_tpu() -> bool:
    import jax

    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def in_dynamic_mode() -> bool:
    """False while static-graph building is enabled (paddle.enable_static)."""
    from .static.graph import in_static_mode

    return not in_static_mode()


def disable_static(place=None):
    from .static.graph import disable_static_mode

    disable_static_mode()


def enable_static():
    """Switch to static-graph building: subsequent ops on static Variables
    record into the default main Program (see paddle_tpu/static/graph.py)."""
    from .static.graph import enable_static_mode

    enable_static_mode()


def grad(*args, **kwargs):
    from .framework.core import grad as _grad

    return _grad(*args, **kwargs)
