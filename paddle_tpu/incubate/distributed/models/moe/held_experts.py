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
width is its own number (and the shared experts', a Layer it is handed, their
own), the experts are SwiGLU, and **the routing rule is something the layer
is given**: a function of the router's float32 logits and ``top_k`` (and of
the layer's per-expert selection bias, where the rule has one) that returns
the chosen experts and their weights. Two rules are here:
:func:`deepseek_v3_rule` — sigmoid scores, the ``top_k`` of score + bias,
weights = the chosen scores over their sum, times a scaling factor — and
:func:`softmax_of_chosen` — the ``top_k`` largest logits, weights = softmax
over those logits only.

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
from typing import Callable, Optional, Tuple

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


def deepseek_v3_rule(norm_topk: bool = True, scale: float = 1.0):
    """DeepSeek-V3's rule (arXiv:2412.19437): scores = sigmoid(logits); the
    ``top_k`` of score + the selection bias are chosen; weights = the chosen
    scores (over their sum, with ``norm_topk``) times ``scale``."""
    def rule(logits, top_k: int, bias):
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), w * scale

    return rule


def softmax_of_chosen(logits, top_k: int, bias=None):
    """The ``top_k`` largest logits are chosen; weights = softmax over those
    ``top_k`` logits only (the granitemoe family's rule). No selection
    bias."""
    if bias is not None:
        raise ValueError("softmax_of_chosen takes no selection bias")
    top, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


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
    their own width), or None; ``rule`` the routing rule, ``(float32 logits
    (T, n_routed_experts), top_k, selection bias) -> (chosen ids (T, k)
    int32, weights (T, k) float32)`` (default: :func:`deepseek_v3_rule`);
    ``selection_bias`` whether the layer holds a per-expert bias for the
    rule (``router_bias``; a rule without one is handed None); ``dtype`` the
    type the expert stacks are drawn in (None: the layer's default,
    float32)."""

    # dropless: there is no capacity (the serving executor keys on this)
    capacity_factor = None

    def __init__(self, d_model: int, d_hidden: int, n_routed_experts: int,
                 top_k: int, experts_held: Optional[Tuple[int, int]] = None,
                 shared: Optional[Layer] = None,
                 rule: Optional[Callable] = None,
                 selection_bias: Optional[bool] = None,
                 init_std: float = 0.02, dtype=None):
        super().__init__()
        lo, hi = experts_held or (0, n_routed_experts)
        if not 0 <= lo < hi <= n_routed_experts:
            raise ValueError(f"experts_held {(lo, hi)} is no range of the "
                             f"{n_routed_experts} routed experts")
        if top_k > n_routed_experts:
            raise ValueError(f"top_k {top_k} > {n_routed_experts} experts")
        self.n_routed_experts, self.top_k = int(n_routed_experts), int(top_k)
        self.experts_held = (int(lo), int(hi))
        if selection_bias is None:
            selection_bias = rule is None
        self.rule = deepseek_v3_rule() if rule is None else rule
        init = _normal(init_std)
        E = hi - lo
        self.router = Linear(d_model, n_routed_experts, bias_attr=False,
                             weight_attr=init)
        self.router_bias = (self.create_parameter(
            [n_routed_experts], default_initializer=init)
            if selection_bias else None)
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
        logits = jnp.matmul(h, self.router.weight.value,
                            preferred_element_type=jnp.float32)
        bias = None if self.router_bias is None else self.router_bias.value
        idx, w = self.rule(logits, self.top_k, bias)
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
