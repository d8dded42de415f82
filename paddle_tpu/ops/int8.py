"""Weight-only int8 matmul for HBM-bound decode.

Reference analogue: the int8 variants of the fused transformer ops
(ref paddle/fluid/operators/fused/fused_multi_transformer_int8_op.cu,
quant_dequant kernels) and the PTQ weight-only path. On TPU the motivation
is sharper: single-token decode re-reads every weight per token, so tokens/s
is bounded by HBM bandwidth / parameter bytes — int8 weights halve the bytes
and nearly double the decode roofline.

Scheme: symmetric per-output-channel absmax. w ≈ w_q(int8) * scale(f32)[N],
and since scale is per *column*, dot(x, w_q·scale) == dot(x, w_q) · scale —
the kernel dots in bf16 (int8 values up to 127 are exact in bf16) and applies
the scale to the fp32 accumulator. The Pallas kernel streams int8 weight
blocks through VMEM (half the bytes of the bf16 path); on CPU the plain jnp
dequant path runs, except under PT_FLASH_INTERPRET=1 where the Pallas
kernel itself executes interpreted (same gate as flash_attention — CI
coverage of the kernel logic without a chip).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

_LANE = 128


def quantize_per_channel(w) -> Tuple[jax.Array, jax.Array]:
    """[K, N] float → ([K, N] int8, [N] f32 scale); symmetric absmax."""
    w = jnp.asarray(w)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return w_q.astype(jnp.int8), scale


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _w8_kernel(x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    s = s_ref[...]  # (1, bn) — 2-D so Mosaic/XLA agree on the layout
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s).astype(o_ref.dtype)


def _w8_matmul_pallas(x2, w_q, scale, out_dtype, block_n: int = 0):
    import os

    from jax.experimental import pallas as pl

    M, K = x2.shape
    N = w_q.shape[1]
    if not block_n:
        try:
            block_n = int(os.environ.get("PT_W8_BLOCK_N", 512))
        except ValueError:
            block_n = 512
        # round down to a power of two in [_LANE, ...]; bad values would
        # either ZeroDivide (0) or shred the grid into tiny blocks
        block_n = max(_LANE, 1 << max(block_n, _LANE).bit_length() - 1)
    bn = min(block_n, N)
    while N % bn:
        bn //= 2
    grid = (N // bn,)
    return pl.pallas_call(
        functools.partial(_w8_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((M, K), lambda j: (0, 0)),
            pl.BlockSpec((K, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=_interpret(),
    )(x2, w_q, scale.reshape(1, N))


def w8_matmul(x, w_q, scale):
    """x [..., K] @ dequant(w_q [K, N], scale [N]) -> [..., N] in x.dtype."""
    x = jnp.asarray(x)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    out_dtype = x.dtype
    # M-gate rationale lives with the rule (select.select_w8_matmul): the
    # streaming kernel is for weight-read-bound decode batches only
    from .select import XLA, record, select_w8_matmul

    if record("w8_matmul", select_w8_matmul(M, K, N)) != XLA:
        return _w8_matmul_pallas(x2, w_q, scale, out_dtype).reshape(*lead, N)
    deq = (w_q.astype(jnp.float32) * scale[None, :]).astype(out_dtype)
    return jnp.matmul(x2, deq).reshape(*lead, N)
