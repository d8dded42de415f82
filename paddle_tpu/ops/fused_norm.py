"""Fused RMS/Layer norm Pallas kernels (ref: the reference fuses norms into
decoder layers inside fused_multi_transformer_op.cu; standalone layer_norm is
phi/kernels/gpu/layer_norm_kernel.cu).

Single-pass row kernels: mean/var computed in VMEM, scaled output written
once. ``select.select_fused_norm`` picks the kernel on a TPU and the jnp
composition elsewhere; a selected kernel that fails to compile raises.
Backward via recompute (jnp composition), same policy as flash_attention.

The row tile comes from ``select.norm_block_rows`` (width, dtype and the
VMEM budget); a swept :class:`~paddle_tpu.autotune.kernel_geometry
.NormGeometry` can only lower it. Every row computes its own statistics,
so any tile is bit-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_ref(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _ln_ref(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(ms + eps) * w_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    o_ref[:] = ((x - mu) * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _block_rows(x, rows, geometry):
    """Row tile for ``x`` flattened to ``rows`` rows; raises when nothing
    fits (``select.select_fused_norm`` keeps such shapes off the kernel)."""
    from ..autotune.kernel_geometry import NormGeometry, resolve_geometry
    from .select import norm_block_rows

    if geometry is None:
        geometry = resolve_geometry("fused_norm", str(x.dtype),
                                    x.shape[-1])[0]
    if not isinstance(geometry, NormGeometry):
        raise ValueError(f"fused norm wants a NormGeometry, got "
                         f"{type(geometry).__name__}")
    geometry.validate()
    tile = norm_block_rows(rows, x.shape[-1], x.dtype, want=geometry.rows)
    if not tile:
        raise ValueError(f"no row tile of {rows} rows fits VMEM at width "
                         f"{x.shape[-1]}")
    return tile


def _select(x):
    from .select import record, select_fused_norm

    return record("fused_norm", select_fused_norm(
        x.size // x.shape[-1], x.shape[-1], x.dtype))


def _rms_pallas(x, weight, eps, geometry=None, interpret=False):
    from jax.experimental import pallas as pl

    D = x.shape[-1]
    flat = x.reshape(-1, D)
    rows = flat.shape[0]
    block_rows = _block_rows(x, rows, geometry)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat, weight)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_rms_norm(x, weight, eps=1e-6):
    if _select(x) == "pallas":
        return _rms_pallas(x, weight, eps)
    return _rms_ref(x, weight, eps)


def _rms_fwd(x, w, eps):
    return fused_rms_norm(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    _, vjp_fn = jax.vjp(lambda x_, w_: _rms_ref(x_, w_, eps), x, w)
    return vjp_fn(g)


fused_rms_norm.defvjp(_rms_fwd, _rms_bwd)


def _ln_pallas(x, weight, bias, eps, geometry=None, interpret=False):
    from jax.experimental import pallas as pl

    D = x.shape[-1]
    flat = x.reshape(-1, D)
    rows = flat.shape[0]
    block_rows = _block_rows(x, rows, geometry)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat, weight, bias)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, weight, bias, eps=1e-5):
    if _select(x) == "pallas":
        return _ln_pallas(x, weight, bias, eps)
    return _ln_ref(x, weight, bias, eps)


def _ln_fwd(x, w, b, eps):
    return fused_layer_norm(x, w, b, eps), (x, w, b)


def _ln_bwd(eps, res, g):
    x, w, b = res
    _, vjp_fn = jax.vjp(lambda x_, w_, b_: _ln_ref(x_, w_, b_, eps), x, w, b)
    return vjp_fn(g)


fused_layer_norm.defvjp(_ln_fwd, _ln_bwd)
