"""LoRA (low-rank adaptation) for training AND batched serving.

Training side: :class:`LoRALinear` wraps an existing :class:`Linear` with a
trainable low-rank residual ``y = xW + (x A) B * (alpha/r)`` — the base
weight is frozen (``trainable=False``) so only the factors flow through the
optimizer. :func:`attach_lora` / :func:`merge_lora` walk a model and
wrap/fold the configured projection attributes in place;
:func:`export_adapter` / :func:`load_adapter` round-trip the factors
through ``.npz`` checkpoints consumable by the serving-side registry
(``inference/lora.py``).

Serving side: :func:`bgmv` is the batched-gathered-matrix-vector delta used
inside the paged decode/verify/prefill programs — per-row A/B factors
(already gathered from the adapter pool by row index) applied as two skinny
matmuls. Factors are stored and applied in f32 regardless of the base
dtype: adapters are tiny and the padded-rank zero columns must stay exact.
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..framework.dispatch import apply_op
from .initializer import Constant, Normal
from .layer.common import Linear
from .layer_base import Layer

__all__ = ["LoRALinear", "attach_lora", "merge_lora", "lora_parameters",
           "lora_state", "load_lora_state", "export_adapter", "load_adapter",
           "bgmv", "lora_matmul"]


class LoRALinear(Layer):
    """A frozen :class:`Linear` plus a trainable rank-``r`` residual.

    ``lora_A`` is Normal(0, 0.02) and ``lora_B`` is zeros, so the wrapped
    layer is numerically identical to the base until training moves B —
    the standard LoRA init that makes attach/detach safe mid-run."""

    def __init__(self, base: Linear, rank: int, alpha: Optional[float] = None):
        super().__init__()
        if rank < 1:
            raise ValueError(f"LoRA rank must be >= 1, got {rank}")
        self.base = base
        self.rank = int(rank)
        self.alpha = float(alpha if alpha is not None else rank)
        self.scaling = self.alpha / self.rank
        in_f, out_f = base._in_features, base._out_features
        base.weight.trainable = False
        base.weight.stop_gradient = True
        if base.bias is not None:
            base.bias.trainable = False
            base.bias.stop_gradient = True
        # factors stay f32 even under a bf16 base: the delta is computed in
        # f32 and cast at the end (matches the serving pool's layout)
        self.lora_A = self.create_parameter([in_f, rank], attr=Normal(0.0, 0.02),
                                            dtype="float32")
        self.lora_B = self.create_parameter([rank, out_f], attr=Constant(0.0),
                                            dtype="float32")

    def forward(self, x):
        s = self.scaling

        def lin(v, w, b, a, bb):
            y = jnp.matmul(v, w)
            if b is not None:
                y = y + b
            d = jnp.matmul(jnp.matmul(v.astype(jnp.float32), a), bb) * s
            return y + d.astype(y.dtype)

        if self.base.bias is not None:
            return apply_op(lambda v, w, b, a, bb: lin(v, w, b, a, bb),
                            x, self.base.weight, self.base.bias,
                            self.lora_A, self.lora_B, op_name="lora_linear")
        return apply_op(lambda v, w, a, bb: lin(v, w, None, a, bb),
                        x, self.base.weight, self.lora_A, self.lora_B,
                        op_name="lora_linear")

    def merged_weight(self) -> np.ndarray:
        """Base weight with the low-rank delta folded in (f32 numpy)."""
        # deliberate host boundary: merge/export runs off the hot path
        w = np.asarray(self.base.weight.value, dtype=np.float32)  # graftlint: noqa[host-sync]
        a = np.asarray(self.lora_A.value, dtype=np.float32)  # graftlint: noqa[host-sync]
        b = np.asarray(self.lora_B.value, dtype=np.float32)  # graftlint: noqa[host-sync]
        return w + self.scaling * (a @ b)

    def extra_repr(self):
        return (f"in_features={self.base._in_features}, "
                f"out_features={self.base._out_features}, rank={self.rank}, "
                f"alpha={self.alpha}")


def _wrap_sites(model: Layer, targets: Iterable[str]):
    """Yield (owner_layer, attr_name, child) for every target attribute that
    is a plain Linear anywhere in the model tree."""
    tset = tuple(targets)
    for _, layer in model.named_sublayers(include_self=True):
        for tname in tset:
            child = layer._sub_layers.get(tname)
            if isinstance(child, LoRALinear):
                yield layer, tname, child
            elif isinstance(child, Linear):
                yield layer, tname, child


def attach_lora(model: Layer, rank: int, alpha: Optional[float] = None,
                targets: Iterable[str] = ()) -> Layer:
    """Replace every ``targets`` attribute that is a plain :class:`Linear`
    with a :class:`LoRALinear` of the given rank. Idempotent on already
    wrapped sites. Returns the model (mutated in place)."""
    n = 0
    for layer, tname, child in list(_wrap_sites(model, targets)):
        if isinstance(child, LoRALinear):
            continue
        setattr(layer, tname, LoRALinear(child, rank, alpha))
        n += 1
    if n == 0 and not any(True for _ in _wrap_sites(model, targets)):
        raise ValueError(f"attach_lora found no Linear targets {tuple(targets)}")
    return model


def merge_lora(model: Layer, targets: Iterable[str] = ()) -> Layer:
    """Fold every LoRALinear's delta into its base weight and put the plain
    Linear back — the inverse of :func:`attach_lora` for inference export."""
    for layer, tname, child in list(_wrap_sites(model, targets)):
        if not isinstance(child, LoRALinear):
            continue
        base = child.base
        merged = child.merged_weight().astype(
            np.asarray(base.weight.value).dtype)  # graftlint: noqa[host-sync]
        base.weight.trainable = True
        base.weight.stop_gradient = False
        base.weight.set_value(merged)
        if base.bias is not None:
            base.bias.trainable = True
            base.bias.stop_gradient = False
        setattr(layer, tname, base)
    return model


def lora_parameters(model: Layer) -> List:
    """The trainable A/B factors — hand this to the optimizer."""
    out = []
    for _, layer in model.named_sublayers(include_self=True):
        if isinstance(layer, LoRALinear):
            out.extend([layer.lora_A, layer.lora_B])
    return out


def lora_state(model: Layer) -> Dict[str, Dict]:
    """{module_path: {"A": f32 ndarray, "B": f32 ndarray}} plus a "__meta__"
    entry carrying rank/alpha — the adapter checkpoint payload."""
    state: Dict[str, Dict] = {}
    meta = None
    for path, layer in model.named_sublayers(include_self=True):
        if not isinstance(layer, LoRALinear):
            continue
        # checkpoint export: the one-off host copy IS the point here
        state[path] = {"A": np.asarray(layer.lora_A.value, dtype=np.float32),  # graftlint: noqa[host-sync]
                       "B": np.asarray(layer.lora_B.value, dtype=np.float32)}  # graftlint: noqa[host-sync]
        if meta is None:
            meta = {"rank": layer.rank, "alpha": layer.alpha}
    if meta is None:
        raise ValueError("model has no LoRALinear layers to export")
    state["__meta__"] = meta
    return state


def load_lora_state(model: Layer, state: Dict[str, Dict]) -> Layer:
    """Restore exported factors into an already-attached model."""
    for path, layer in model.named_sublayers(include_self=True):
        if isinstance(layer, LoRALinear) and path in state:
            layer.lora_A.set_value(np.asarray(state[path]["A"], np.float32))
            layer.lora_B.set_value(np.asarray(state[path]["B"], np.float32))
    return model


def export_adapter(model: Layer, path: str) -> None:
    """Save the adapter checkpoint as ``.npz`` (keys ``A:<module path>`` /
    ``B:<module path>`` + json meta)."""
    state = lora_state(model)
    meta = state.pop("__meta__")
    arrays = {}
    for mpath, ab in state.items():
        arrays[f"A:{mpath}"] = ab["A"]
        arrays[f"B:{mpath}"] = ab["B"]
    arrays["__meta__"] = np.frombuffer(
        json.dumps(meta).encode("utf-8"), dtype=np.uint8)
    np.savez(path, **arrays)


def load_adapter(path: str) -> Dict[str, Dict]:
    """Load an ``.npz`` adapter checkpoint back into the
    :func:`lora_state` dict shape (consumable by ``load_lora_state`` or
    ``inference.lora.AdapterRegistry.register``)."""
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"].tobytes()).decode("utf-8"))
        state: Dict[str, Dict] = {"__meta__": meta}
        for key in z.files:
            if key.startswith("A:"):
                mpath = key[2:]
                state[mpath] = {"A": np.asarray(z[key], np.float32),
                                "B": np.asarray(z[f"B:{mpath}"], np.float32)}
    return state


# --------------------------------------------------------------------------- #
# serving-side batched delta
# --------------------------------------------------------------------------- #


def bgmv(x: Tensor, ab: Optional[Tuple]) -> Optional[Tensor]:
    """Batched gathered LoRA delta: ``ab = (A, B, scale)`` raw jnp arrays
    already gathered per row — A (B, in, R), B (B, R, out), scale (B,) with
    alpha/r pre-baked (scale 0 and zero factors on the null page make
    adapterless rows exact no-ops). x: Tensor (B, S, in). Returns the delta
    Tensor (B, S, out) in x's dtype; compute is f32 so the zero-padded rank
    columns cancel exactly."""
    if ab is None:
        return None
    A, B, s = ab

    def f(v, a, b, sc):
        d = jnp.einsum("bsh,bhr->bsr", v.astype(jnp.float32), a)
        d = jnp.einsum("bsr,bro->bso", d, b) * sc[:, None, None]
        return d.astype(v.dtype)

    return apply_op(f, x, Tensor(A), Tensor(B), Tensor(s), op_name="lora_bgmv")


def lora_matmul(x: Tensor, w: Tensor, ab: Optional[Tuple]) -> Tensor:
    """Base projection + gathered LoRA delta in ONE op:
    ``x @ w + ((x32 @ A) @ B) * scale`` with ``ab = (A, B, scale)`` as in
    :func:`bgmv` (None means plain matmul). Where the kernel is selected
    (``ops.select.select_lora_matmul``) the whole expression runs as one Pallas
    program per batch row (``ops.paged_attention_pallas.fused_lora_matmul``)
    so multi-tenant decode stops paying a separate gather+matmul pass; the
    jnp composition is bit-identical to the Linear-then-:func:`bgmv`
    sequence it replaces (same primitives, same order)."""
    if ab is None:
        return apply_op(lambda v, wv: jnp.matmul(v, wv), x, w,
                        op_name="linear")
    A, B, s = ab

    def f(v, wv, a, b, sc):
        from ..ops.select import XLA, record, select_lora_matmul

        if record("lora_matmul", select_lora_matmul(
                v.shape, wv.shape, a.shape[2], v.dtype, wv.dtype)) != XLA:
            from ..ops.paged_attention_pallas import fused_lora_matmul
            return fused_lora_matmul(v, wv, a, b, sc)
        y = jnp.matmul(v, wv)
        d = jnp.einsum("bsh,bhr->bsr", v.astype(jnp.float32), a)
        d = jnp.einsum("bsr,bro->bso", d, b) * sc[:, None, None]
        return y + d.astype(v.dtype)

    return apply_op(f, x, w, Tensor(A), Tensor(B), Tensor(s),
                    op_name="lora_linear")
