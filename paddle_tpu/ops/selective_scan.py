"""Selective state-space scan (Mamba-1) for the serving path.

The recurrence, per channel ``c`` of ``d_inner`` and state ``s`` of
``d_state``, all in float32::

    h_t[s, c] = exp(dt_t[c] * A[s, c]) * h_{t-1}[s, c] + dt_t[c] * x_t[c] * B_t[s]
    y_t[c]    = sum_s h_t[s, c] * C_t[s] + D[c] * x_t[c]

The state is laid out ``(d_state, d_inner)`` — channels on the lanes — so a
tile of it fills whole vector registers (``d_state`` is 16: state-minor would
use 16 of 128 lanes).

Two entry points, one per program of the serving executor:

- :func:`ssm_step`: ONE token for every slot (decode). Rows whose ``dt`` is
  zero keep their state exactly (``exp(0) = 1`` and a zero increment), which
  is how the caller masks idle and prefilling slots.
- :func:`ssm_chunk_scan`: a prefill chunk of one slot, sequentially over its
  tokens, from a state and to a state. Tokens past the chunk's valid length
  are given ``dt = 0`` by the caller, so the returned state is the state
  after the last valid token.

Each has a Pallas kernel (selected by ``select.select_selective_scan``) and
the jnp composition it is tested against (``*_ref``). The causal
convolution in front of the scan is a four-tap depthwise filter: it stays in
jnp (:func:`causal_conv_step`, :func:`causal_conv_chunk`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ----------------------------------------------------------------- convolution
def causal_conv_step(tail, x, w, b):
    """One token per row. tail (B, K-1, d): the last K-1 inputs, oldest
    first; x (B, d); w (K, d); b (d). Returns (y (B, d) f32, new tail)."""
    win = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.einsum("bkd,kd->bd", win.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return y, win[:, 1:]


def causal_conv_chunk(tail, x, w, b, n_valid):
    """A chunk of one row. tail (K-1, d); x (C, d); the new tail is the last
    K-1 inputs up to the chunk's ``n_valid``-th token (traced int)."""
    K = w.shape[0]
    C = x.shape[0]
    seq = jnp.concatenate([tail, x.astype(tail.dtype)], axis=0)  # (K-1+C, d)
    sf, wf = seq.astype(jnp.float32), w.astype(jnp.float32)
    y = sum(sf[k:k + C] * wf[k] for k in range(K)) + b.astype(jnp.float32)
    return y, jax.lax.dynamic_slice_in_dim(seq, n_valid, K - 1, 0)


# ------------------------------------------------------------------ references
def ssm_step_ref(x, dt, A, Bm, Cm, D, h):
    """x, dt (B, d) f32; A (S, d); Bm, Cm (B, S); D (d); h (B, S, d) f32.
    Returns (y (B, d) f32, h' (B, S, d))."""
    dA = jnp.exp(dt[:, None, :] * A[None])
    h = dA * h + (dt * x)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(h * Cm[:, :, None], axis=1) + D[None] * x
    return y, h


def ssm_chunk_scan_ref(x, dt, A, Bm, Cm, D, h0):
    """x, dt (T, d) f32; Bm, Cm (T, S); h0 (S, d). ``lax.scan`` over T."""
    def one(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[None] * A) * h + (dtt * xt)[None] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + D * xt

    hT, y = jax.lax.scan(one, h0, (x, dt, Bm, Cm))
    return y, hT


# --------------------------------------------------------------------- kernels
def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _step_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h_ref, y_ref,
                 ho_ref):
    x, dt = x_ref[...], dt_ref[...]                   # (nb, 1, td)
    h = jnp.exp(dt * a_ref[...][None]) * h_ref[...] + (dt * x) * b_ref[...]
    ho_ref[...] = h
    y_ref[...] = (jnp.sum(h * c_ref[...], axis=1, keepdims=True)
                  + d_ref[...][None] * x)


def ssm_step_pallas(x, dt, A, Bm, Cm, D, h, rows=8, lanes=1024):
    """Fused one-step update over all slots: reads and writes the state
    once, in place (``h`` is aliased to the output)."""
    B, S, d = h.shape
    nb = rows if B % rows == 0 else 1
    td = lanes if d % lanes == 0 else d
    x3, dt3 = x.reshape(B, 1, d), dt.reshape(B, 1, d)

    def row(i, j):
        return (i, 0, j)

    y, h = pl.pallas_call(
        _step_kernel,
        grid=(B // nb, d // td),
        in_specs=[pl.BlockSpec((nb, 1, td), row),
                  pl.BlockSpec((nb, 1, td), row),
                  pl.BlockSpec((S, td), lambda i, j: (0, j)),
                  pl.BlockSpec((nb, S, 1), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((nb, S, 1), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, td), lambda i, j: (0, j)),
                  pl.BlockSpec((nb, S, td), row)],
        out_specs=[pl.BlockSpec((nb, 1, td), row),
                   pl.BlockSpec((nb, S, td), row)],
        out_shape=[jax.ShapeDtypeStruct((B, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, d), jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(), name="ssm_step",
    )(x3, dt3, A, Bm[:, :, None], Cm[:, :, None], D[None], h)
    return y.reshape(B, d), h


def _chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref,
                  hT_ref, *, T):
    a, dd = a_ref[...], d_ref[...]                    # (S, td), (1, td)

    def one(t, h):
        xt, dtt = x_ref[pl.ds(t, 1), :], dt_ref[pl.ds(t, 1), :]   # (1, td)
        h = jnp.exp(dtt * a) * h + (dtt * xt) * b_ref[t]          # (S, td)
        y_ref[pl.ds(t, 1), :] = (jnp.sum(h * c_ref[t], axis=0, keepdims=True)
                                 + dd * xt)
        return h

    hT_ref[...] = jax.lax.fori_loop(0, T, one, h0_ref[...])


def ssm_chunk_scan_pallas(x, dt, A, Bm, Cm, D, h0, lanes=512):
    """One chunk, sequential over its T tokens, one program per tile of
    channels: the state tile stays in registers/VMEM for the whole chunk, so
    HBM sees x, dt and y once and the state twice."""
    T, d = x.shape
    S = A.shape[0]
    td = lanes if d % lanes == 0 else d
    y, hT = pl.pallas_call(
        functools.partial(_chunk_kernel, T=T),
        grid=(d // td,),
        in_specs=[pl.BlockSpec((T, td), lambda j: (0, j)),
                  pl.BlockSpec((T, td), lambda j: (0, j)),
                  pl.BlockSpec((S, td), lambda j: (0, j)),
                  pl.BlockSpec((T, S, 1), lambda j: (0, 0, 0)),
                  pl.BlockSpec((T, S, 1), lambda j: (0, 0, 0)),
                  pl.BlockSpec((1, td), lambda j: (0, j)),
                  pl.BlockSpec((S, td), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((T, td), lambda j: (0, j)),
                   pl.BlockSpec((S, td), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.float32),
                   jax.ShapeDtypeStruct((S, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(), name="ssm_chunk_scan",
    )(x, dt, A, Bm[:, :, None], Cm[:, :, None], D[None], h0)
    return y, hT


# ---------------------------------------------------------------- entry points
def ssm_step(x, dt, A, Bm, Cm, D, h):
    """Decode: one token for every slot; see the module docstring."""
    from .select import XLA, record, select_selective_scan

    if record("ssm_step", select_selective_scan(h.shape)) == XLA:
        return ssm_step_ref(x, dt, A, Bm, Cm, D, h)
    return ssm_step_pallas(x, dt, A, Bm, Cm, D, h)


def ssm_chunk_scan(x, dt, A, Bm, Cm, D, h0):
    """Prefill: one chunk of one slot; see the module docstring."""
    from .select import XLA, record, select_selective_scan

    if record("ssm_chunk_scan",
              select_selective_scan((1,) + h0.shape, tokens=x.shape[0])
              ) == XLA:
        return ssm_chunk_scan_ref(x, dt, A, Bm, Cm, D, h0)
    return ssm_chunk_scan_pallas(x, dt, A, Bm, Cm, D, h0)
