"""Inference deployment (ref: paddle/fluid/inference/ — AnalysisPredictor
api/analysis_predictor.cc:929 Run, AnalysisConfig, pass pipeline :1315).

TPU-native redesign: the IR-pass pipeline (ir_analysis_pass, memory-optimize,
TensorRT subgraphs) is XLA's job. What remains of the capability:
- Config: predictor configuration surface (API parity),
- Predictor: AOT-compiled callable (jax.jit lowered+compiled once at load),
- export/load via jax.export StableHLO serialization — the deployable
  artifact (the analogue of the serialized inference program + params).
"""
from __future__ import annotations

import os
import pickle
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..jit import functional_call, state_values


class Config:
    """AnalysisConfig parity (the GPU/TensorRT/MKLDNN knobs become no-ops —
    XLA owns those decisions on TPU)."""

    def __init__(self, model_dir: Optional[str] = None,
                 prog_file: Optional[str] = None, params_file: Optional[str] = None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.params_file = params_file
        self._use_tpu = True
        self._memory_optim = True
        self._ir_optim = True

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        pass

    def disable_gpu(self):
        pass

    def enable_memory_optim(self):
        self._memory_optim = True

    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def enable_tensorrt_engine(self, *a, **k):
        raise NotImplementedError("TensorRT is CUDA-only; XLA compiles on TPU")

    def set_cpu_math_library_num_threads(self, n):
        pass


class Predictor:
    """AnalysisPredictor parity: compiled forward with named input/output
    handles (ref analysis_predictor.cc Run :929)."""

    def __init__(self, fn, params, input_names: Sequence[str],
                 example_inputs: Sequence[Any]):
        self._params = params
        self._input_names = list(input_names)
        self._inputs: Dict[str, Any] = {}
        self._outputs: List[Any] = []
        self._compiled = jax.jit(fn)
        # warm compile with example inputs
        if example_inputs:
            out = self._compiled(params, *example_inputs)
            jax.block_until_ready(out)

    @classmethod
    def from_layer(cls, layer, example_inputs: Sequence[Any],
                   input_names: Optional[Sequence[str]] = None):
        params = state_values(layer)
        layer.eval()

        def fn(params, *args):
            out = functional_call(layer, params, *[Tensor(a) for a in args])
            return jax.tree_util.tree_map(
                lambda t: t.value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))

        names = list(input_names) if input_names else \
            [f"input_{i}" for i in range(len(example_inputs))]
        ex = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
              for a in example_inputs]
        return cls(fn, params, names, ex)

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        pred = self

        class _Handle:
            def copy_from_cpu(self, arr):
                pred._inputs[name] = jnp.asarray(arr)

            def reshape(self, shape):
                pass

        return _Handle()

    def get_output_names(self):
        return [f"output_{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name):
        idx = int(name.split("_")[-1])
        pred = self

        class _Handle:
            def copy_to_cpu(self):
                return np.asarray(pred._outputs[idx])

        return _Handle()

    def run(self, inputs: Optional[Sequence[Any]] = None):
        if inputs is None:
            inputs = [self._inputs[n] for n in self._input_names]
        else:
            inputs = [i.value if isinstance(i, Tensor) else jnp.asarray(i)
                      for i in inputs]
        out = self._compiled(self._params, *inputs)
        self._outputs = list(out) if isinstance(out, (list, tuple)) else [out]
        return [np.asarray(o) for o in self._outputs]

    __call__ = run


def create_predictor(config_or_layer, example_inputs=None, **kw) -> Predictor:
    if isinstance(config_or_layer, Config):
        return load_predictor(config_or_layer.model_dir)
    return Predictor.from_layer(config_or_layer, example_inputs or [], **kw)


# --------------------------------------------------------------------------- #
# AOT export (StableHLO) — the deployable artifact
# --------------------------------------------------------------------------- #


def _unwrap_out(out):
    return jax.tree_util.tree_map(
        lambda t: t.value if isinstance(t, Tensor) else t, out,
        is_leaf=lambda t: isinstance(t, Tensor))


def _write_artifact(fn, params, example_inputs, path, meta_extra=None):
    """Trace fn(params, *inputs), serialize StableHLO + params + meta —
    the one artifact format load_predictor consumes."""
    from jax import export as jexport

    ex = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
          for a in example_inputs]
    exported = jexport.export(jax.jit(fn))(
        jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params),
        *[jax.ShapeDtypeStruct(e.shape, e.dtype) for e in ex])
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "model.stablehlo"), "wb") as f:
        f.write(exported.serialize())
    with open(os.path.join(path, "params.pkl"), "wb") as f:
        pickle.dump(jax.tree_util.tree_map(np.asarray, params), f)
    with open(os.path.join(path, "meta.pkl"), "wb") as f:
        pickle.dump({"n_inputs": len(ex), **(meta_extra or {})}, f)
    return path


def export_model(layer, example_inputs: Sequence[Any], path: str):
    """Serialize weights + StableHLO of the jitted forward (ref: the saved
    inference program; jax.export replaces ProgramDesc+params files)."""
    layer.eval()
    params = state_values(layer)

    def fn(params, *args):
        return _unwrap_out(
            functional_call(layer, params, *[Tensor(a) for a in args]))

    return _write_artifact(fn, params, example_inputs, path)


def export_quantized_model(layer, example_inputs: Sequence[Any], path: str,
                           quantizable=None, skip_patterns=None):
    """Quantized-program export (the reference's int8 quantizer pipeline,
    ref inference/api/mkldnn_quantizer.cc, done the TPU way): serialized
    params are per-output-channel INT8 weights, and the traced StableHLO
    program dequantizes in-graph — int8 weights live in HBM (half the
    artifact/transfer of bf16, quarter of fp32) and XLA fuses the dequant
    into the consuming matmul (the weight-only int8 serving path). Loads
    with the same
    :func:`load_predictor`."""
    from jax import export as jexport

    from ..static.quantization import (channelwise_quant_int8,
                                       select_quantizable)

    layer.eval()
    params = state_values(layer)
    np_params = {n: np.asarray(v) for n, v in params.items()}
    # scope: >=2D floating parameters (not buffers), embedding-family names
    # excluded by default — mirror of quant_post_static's quantizable_op_type
    # contract; override with quantizable=/skip_patterns=
    to_quant = select_quantizable(
        np_params, quantizable=quantizable, skip_patterns=skip_patterns,
        param_names={n for n, _ in layer.named_parameters()})
    qparams: Dict[str, Any] = {}
    scales: Dict[str, Any] = {}
    for name, arr in np_params.items():
        if name in to_quant:
            q, sc, bshape = channelwise_quant_int8(
                arr.astype(np.float32) if arr.dtype != np.float32 else arr)
            qparams[name] = q
            scales[name] = (jnp.asarray(sc.reshape(bshape)), arr.dtype)
        else:
            qparams[name] = arr
    assert scales, (
        "no quantizable weights: every >=2D floating parameter was excluded "
        "by the default scope (embedding-family names and buffers are "
        "skipped) — pass quantizable=[names]/predicate or skip_patterns=() "
        "to widen it")

    def fn(qp, *args):
        deq = {}
        for name, v in qp.items():
            if name in scales:
                sc, dt = scales[name]  # scales are program constants
                deq[name] = (v.astype(jnp.float32) * sc).astype(dt)
            else:
                deq[name] = v
        return _unwrap_out(
            functional_call(layer, deq, *[Tensor(a) for a in args]))

    return _write_artifact(fn, qparams, example_inputs, path,
                           meta_extra={"quantized": "int8-weight-only"})


def load_predictor(path: str) -> Predictor:
    from jax import export as jexport

    with open(os.path.join(path, "model.stablehlo"), "rb") as f:
        exported = jexport.deserialize(f.read())
    with open(os.path.join(path, "params.pkl"), "rb") as f:
        params = pickle.load(f)
    with open(os.path.join(path, "meta.pkl"), "rb") as f:
        meta = pickle.load(f)

    def fn(params, *args):
        return exported.call(params, *args)

    names = [f"input_{i}" for i in range(meta["n_inputs"])]
    return Predictor(fn, params, names, [])


from .autoscale import (AutoscalePolicy, ElasticAutoscaler,  # noqa: E402,F401
                        FleetAutoscaler, ScaleDecision, verify_replay)
from .faults import (NULL_INJECTOR, EngineFailedError,  # noqa: E402,F401
                     FaultInjector, FaultPlan, FaultSpec, TickFault)
from .fleet import (REPLICA_DEAD, REPLICA_DEGRADED,  # noqa: E402,F401
                    REPLICA_DRAINING, REPLICA_LIVE, RID_STRIDE,
                    FleetRouter, ReplicaInfo)
from .kv_offload import (HostKVPool, KVOffloadEngine,  # noqa: E402,F401
                         SwapHandle, payload_checksum)
from .lora import (Adapter, AdapterPool, AdapterRegistry,  # noqa: E402,F401
                   LoRAConfig, adapter_page_bytes)
from .paged_cache import BlockAllocator  # noqa: E402,F401
from .scheduler import (PRIORITY_HIGH, PRIORITY_LOW,  # noqa: E402,F401
                        PRIORITY_NORMAL, AdmissionError, SchedEntry,
                        Scheduler)
from .serving import GenerationServer  # noqa: E402,F401
from .speculative import (DrafterFault, DraftModelDrafter,  # noqa: E402,F401
                          NgramDrafter, SpecConfig)
from .telemetry import (FlightRecorder, MetricsRegistry,  # noqa: E402,F401
                        ServingTelemetry, SpanTracer, watchdog)
from .transport import (InProcessReplica, RemoteReplicaError,  # noqa: E402,F401
                        ReplicaHandle, ReplicaTransportError,
                        SubprocessReplica)
