"""An expert layer that is told WHICH experts it holds.

The layer of a many-expert model as one chip of an expert-parallel group
runs it: the router keeps its published width and routes every token over
ALL experts; the chip holds the weights of a contiguous range of them
(``experts_held = (lo, hi)``) and computes, for each token, the weighted sum
over the chosen experts that live here — plus the shared experts, which
every chip computes for its own rows. What the absent experts would have
added is their chips' to compute and is left out: no exchange, and nothing
that stands in for one.

Beside :class:`~.moe_layer.MoELayer` and unlike it: **no capacity and no
dropped token** (``capacity_factor`` is None, which is also what the serving
executor asks before it joins rows of two requests in one call), the expert
width is its own number, the experts are SwiGLU, and routing is DeepSeek-V3's
— sigmoid scores in float32, the ``top_k`` of score + a per-expert selection
bias, weights = the chosen scores over their sum, times a scaling factor.

The product: the (token, expert) pairs are sorted by expert with the pairs
of absent experts (and of rows that are padding) last; the held experts'
three matrices meet their rows in a grouped matrix product
(``ops/grouped_matmul.py``) whose work follows the rows each held expert
got; the weighted outputs return to token order and add up in float32.
The per-expert row counts come back beside the output (``(hi - lo) + 1``
int32: the last entry counts the real rows' pairs whose expert lives
elsewhere) for the caller's counters.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .....framework.core import Tensor
from .....nn.layer.common import Linear
from .....nn.layer_base import Layer


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _normal(std):
    """normal(0, std) drawn and cast in ONE program: a stack of 32 experts
    is 268 M values, and the eager initializer's float32 draw, product and
    cast (1.07 GB each, three stacks in flight) filled the chip beside five
    layers already built (my chip runs, PR 33: peak 16.2 GB)."""
    def init(shape, dtype):
        from .....nn.initializer import next_key

        return _draw(next_key(), tuple(shape), std, jnp.dtype(dtype))
    return init


def route(h, router_w, router_b, top_k: int, norm_topk: bool, scale: float):
    """h (T, d) -> (chosen expert ids (T, k) int32, their weights (T, k)
    float32) over the router's whole width; scores in float32."""
    g = jnp.matmul(h, router_w, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(g.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + router_b.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def held_expert_sum(h, idx, w, w_gate, w_up, w_down, lo: int, valid=None):
    """sum over a token's chosen experts e with lo <= e < lo + E_held of
    ``w * SwiGLU_e(h)``: (T, d) float32, and the row counts (E_held + 1,)."""
    from .....ops.grouped_matmul import grouped_matmul

    T, k = idx.shape
    E = w_gate.shape[0]
    real = jnp.ones((T,), bool) if valid is None else valid
    e = idx - lo
    held = (e >= 0) & (e < E) & real[:, None]
    key = jnp.where(held, e, E).reshape(-1)                 # (T*k,)
    counts = jnp.sum(jax.nn.one_hot(key, E + 1, dtype=jnp.int32)
                     * jnp.repeat(real, k)[:, None].astype(jnp.int32), axis=0)
    order = jnp.argsort(key, stable=True)
    sizes = counts[:E]
    xs = jnp.take(h, order // k, axis=0)                    # pairs by expert
    with jax.named_scope("expert_gmm"):
        act = (jax.nn.silu(grouped_matmul(xs, w_gate, sizes))
               * grouped_matmul(xs, w_up, sizes)).astype(h.dtype)
        out = grouped_matmul(act, w_down, sizes)            # (T*k, d) f32
    in_group = jnp.arange(T * k) < jnp.sum(sizes)
    out = jnp.where(in_group[:, None],
                    out * jnp.take(w.reshape(-1), order)[:, None], 0.0)
    # back to (token, choice) order; a token's parts add up in float32
    back = jnp.take(out, jnp.argsort(order), axis=0)
    return back.reshape(T, k, -1).sum(axis=1), counts


class HeldExpertsLayer(Layer):
    """``n_routed_experts`` is the router's width; ``experts_held`` the
    half-open range whose weights live here (default: all of them);
    ``d_hidden`` one expert's width; ``shared`` the shared experts as one
    Layer from a (T, d) Tensor to a (T, d) Tensor (a SwiGLU of width
    ``n_shared_experts * d_hidden``), or None; ``dtype`` the type the expert
    stacks are drawn in (None: the layer's default, float32)."""

    # dropless: there is no capacity (the serving executor keys on this)
    capacity_factor = None

    def __init__(self, d_model: int, d_hidden: int, n_routed_experts: int,
                 top_k: int, experts_held: Optional[Tuple[int, int]] = None,
                 shared: Optional[Layer] = None, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0, init_std: float = 0.02,
                 dtype=None):
        super().__init__()
        lo, hi = experts_held or (0, n_routed_experts)
        if not 0 <= lo < hi <= n_routed_experts:
            raise ValueError(f"experts_held {(lo, hi)} is no range of the "
                             f"{n_routed_experts} routed experts")
        if top_k > n_routed_experts:
            raise ValueError(f"top_k {top_k} > {n_routed_experts} experts")
        self.n_routed_experts, self.top_k = int(n_routed_experts), int(top_k)
        self.experts_held = (int(lo), int(hi))
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        init = _normal(init_std)
        E = hi - lo
        self.router = Linear(d_model, n_routed_experts, bias_attr=False,
                             weight_attr=init)
        self.router_bias = self.create_parameter(
            [n_routed_experts], default_initializer=init)
        self.experts_gate = self.create_parameter(
            [E, d_model, d_hidden], dtype=dtype, default_initializer=init)
        self.experts_up = self.create_parameter(
            [E, d_model, d_hidden], dtype=dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [E, d_hidden, d_model], dtype=dtype, default_initializer=init)
        self.shared = shared

    def routed(self, h, valid=None):
        """The routed part alone for raw h (T, d): ((T, d) float32,
        counts)."""
        idx, w = route(h, self.router.weight.value, self.router_bias.value,
                       self.top_k, self.norm_topk_prob,
                       self.routed_scaling_factor)
        return held_expert_sum(h, idx, w, self.experts_gate.value,
                               self.experts_up.value, self.experts_down.value,
                               self.experts_held[0], valid)

    def forward(self, h, valid=None):
        """h raw (T, d) in the model's dtype; ``valid`` (T,) bool marks the
        real rows (None: all). Returns (this share's sum (T, d) in h's
        dtype, counts (E_held + 1,) int32)."""
        y, counts = self.routed(h, valid)
        if self.shared is not None:
            y = y + self.shared(Tensor(h)).value.astype(jnp.float32)
        return y.astype(h.dtype), counts
