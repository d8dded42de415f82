"""Fused Adam/AdamW parameter update — one Pallas pass over HBM.

Why: profiling the 509M bench step on a real v5e (2026-07-31, Chrome trace
via jax.profiler) showed XLA's per-tensor `subtract_convert_fusion`
optimizer updates taking ~102 ms of a 377 ms train step — ~12x the
~0.3 ms/tensor HBM bound for what is a purely bandwidth-limited
elementwise pass (read p/g/m/v, write p/m/v).  This kernel streams
(block_k, block_n) tiles through VMEM once and emits all three (or four,
with a master weight) outputs from the same pass.

Reference analogue: the fused Adam/AdamW CUDA kernels
(ref paddle/phi/kernels/gpu/adamw_kernel.cu, fused multi-tensor adam) —
on TPU the fusion is a Pallas elementwise kernel instead of a
multi-tensor CUDA launch.

Semantics match `AdamW._apply_adamw` / `Adam._apply_one` exactly
(decoupled decay applied to the master/param BEFORE the moment update,
bias correction by traced step count).  `_reference_update` is the source
of truth for the XLA fallback and the tests; the kernel body re-expresses
the same math with the bias corrections precomputed (Mosaic cannot
legalize powf with a traced exponent) — edits to the update rule must
touch BOTH, and the interpreted test pins them together.

Measured outcome (2026-07-31, same-window A/B on the 509M bench step):
fused 0.6344 MFU vs unfused 0.6727 — the fused kernel is ~6% SLOWER end
to end despite each XLA update fusion running ~12x its isolated HBM
bound, because XLA *overlaps* those per-tensor updates with backward
compute (trace: 430 ms of device-op time inside a 377 ms step) and ~50
custom calls break that overlap.  The kernel is therefore OPT-IN ONLY
(PT_FUSED_ADAMW=1); the default path stays on XLA's fusions.

The overlap-preserving candidate — ONE multi-tensor launch for all params
(flat_adamw_update + AdamW PT_MT_ADAMW=1, the reference's
multi_tensor_adam / distributed_fused_lamb.py design) — was built and
measured round 4 (2026-07-31, bracketed same-window A/B, identical loss):
default 0.6755 / 0.6756 MFU vs flat 0.5911 / 0.5916.  It loses ~12.5%:
the single launch can only start after the LAST gradient exists, adding
~53 ms of serialized grad-concat + flat-kernel + param-split traffic
(~36 B/param ≈ 18 GB at 509M) to a 376 ms step, while XLA's per-tensor
updates cost ~nothing on the critical path because they overlap backward.
CONCLUSION (thread closed): on TPU + XLA, optimizer updates are not a
launch-count problem — scheduling beats fusion.  Both kernels stay
opt-in for profiling; the default path is XLA's overlapped fusions.

Sharding caveat: a pallas_call is not GSPMD-partitionable, so inside a
pjit over a multi-device mesh it would force a gather of the (possibly
ZeRO-sharded) optimizer state — another reason the kernel never
self-enables.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_LANE = 128
_SUBLANE = 8


def _use_pallas() -> bool:
    from .select import use_pallas

    return use_pallas()


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def usable(shape) -> bool:
    if os.environ.get("PT_FUSED_ADAMW") != "1":
        return False  # opt-in only; measured slower than XLA's overlapped
        # per-tensor fusions on the full train step (see module docstring)
    return multi_tensor_usable(shape)


def _reference_update(param_f32, grad_f32, m, v, lr, b1, b2, eps, decay,
                      step):
    """The exact Adam(W) math both paths implement.  ``decay=0`` is plain
    Adam; ``param_f32`` is the master weight (or the upcast param)."""
    master = param_f32 * (1.0 - lr * decay)
    m2 = b1 * m + (1.0 - b1) * grad_f32
    v2 = b2 * v + (1.0 - b2) * grad_f32 * grad_f32
    mhat = m2 / (1.0 - b1 ** step)
    vhat = v2 / (1.0 - b2 ** step)
    new_master = master - lr * mhat / (jnp.sqrt(vhat) + eps)
    return new_master, m2, v2


def _make_kernel(b1, b2, eps, decay, has_master):
    # the bias corrections 1/(1 - beta**step) arrive precomputed in the
    # scalar block: Mosaic cannot legalize powf with a traced exponent
    def kernel(*refs):
        if has_master:
            (sc_ref, p_ref, g_ref, m_ref, v_ref, mw_ref,
             po_ref, mo_ref, vo_ref, mwo_ref) = refs
            pf = mw_ref[...]
        else:
            (sc_ref, p_ref, g_ref, m_ref, v_ref,
             po_ref, mo_ref, vo_ref) = refs
            pf = p_ref[...].astype(jnp.float32)
        lr = sc_ref[0, 0]
        inv_bc1 = sc_ref[0, 1]
        inv_bc2 = sc_ref[0, 2]
        gf = g_ref[...].astype(jnp.float32)
        master = pf * (1.0 - lr * decay)
        m2 = b1 * m_ref[...] + (1.0 - b1) * gf
        v2 = b2 * v_ref[...] + (1.0 - b2) * gf * gf
        mhat = m2 * inv_bc1
        vhat = v2 * inv_bc2
        new_master = master - lr * mhat / (jnp.sqrt(vhat) + eps)
        po_ref[...] = new_master.astype(po_ref.dtype)
        mo_ref[...] = m2
        vo_ref[...] = v2
        if has_master:
            mwo_ref[...] = new_master
    return kernel


def _pick(dim: int, target: int, unit: int) -> int:
    b = min(target, dim)
    while dim % b:
        b -= unit
        if b < unit:
            return dim
    return b


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "decay",
                                             "has_master"))
def _fused_call(param, grad, m, v, master, scalars, b1, b2, eps, decay,
                has_master):
    from jax.experimental import pallas as pl

    K, N = param.shape
    bn = _pick(N, 512, _LANE)
    # working set ~30 bytes/elem (f32 grad) x2 double buffering must stay
    # well under the 16M scoped-vmem limit
    bk = _pick(K, max(_SUBLANE, (3 * 1024 * 1024 // (30 * bn))
                      // _SUBLANE * _SUBLANE), _SUBLANE)
    grid = (K // bk, N // bn)
    tile = pl.BlockSpec((bk, bn), lambda i, j: (i, j))
    sc = pl.BlockSpec((1, 4), lambda i, j: (0, 0))

    ins = [scalars, param, grad, m, v]
    in_specs = [sc, tile, tile, tile, tile]
    outs = [jax.ShapeDtypeStruct((K, N), param.dtype),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32)]
    out_specs = [tile, tile, tile]
    if has_master:
        ins.append(master)
        in_specs.append(tile)
        outs.append(jax.ShapeDtypeStruct((K, N), jnp.float32))
        out_specs.append(tile)
    return pl.pallas_call(
        _make_kernel(b1, b2, eps, decay, has_master),
        grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=outs,
        interpret=_interpret(),
    )(*ins)


def _scalars(lr, step, b1, b2):
    """(1, 4) scalar block [lr, 1/(1-b1^t), 1/(1-b2^t), 0] — the bias
    corrections arrive precomputed (see _make_kernel)."""
    step_f = jnp.asarray(step, jnp.float32)
    return jnp.stack(
        [jnp.asarray(lr, jnp.float32),
         1.0 / (1.0 - jnp.asarray(b1, jnp.float32) ** step_f),
         1.0 / (1.0 - jnp.asarray(b2, jnp.float32) ** step_f),
         jnp.float32(0.0)]).reshape(1, 4)


def multi_tensor_usable(shape) -> bool:
    """The FLAT multi-tensor apply has its own knob (PT_MT_ADAMW, read by
    the optimizer) — this only checks kernel viability
    (``select.select_fused_adamw``): TPU, tiled 2-D shape, single device
    (a pallas custom call is not GSPMD-partitionable and would gather
    ZeRO-sharded state; interpret mode is the CPU-CI seam)."""
    from .select import XLA, select_fused_adamw

    return select_fused_adamw(shape) != XLA


def flat_adamw_update(param, grad, m, v, *, lr, step, b1, b2, eps, decay
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ONE kernel launch updating the whole model: operands are the
    CONCATENATED flat (K, N) views of every trainable tensor (built once by
    the optimizer; zero-padded tail rows are fixed points of the update).

    This is the overlap-preserving alternative the round-3 per-tensor
    experiment identified (ref distributed_fused_lamb.py / multi_tensor_adam
    — the reference's multi-tensor precedent): ~50 per-tensor custom calls
    broke XLA's backward/update overlap; a single launch pays one
    serialization point and streams all state at the HBM roofline.
    Runs the identical XLA math where the kernel is not selected (CPU
    tests train through that path bit-compatibly).
    """
    param = jnp.asarray(param)
    grad = jnp.asarray(grad)
    if multi_tensor_usable(param.shape):
        out = _fused_call(param, grad, m, v, None, _scalars(lr, step, b1, b2),
                          float(b1), float(b2), float(eps), float(decay),
                          False)
        return out[0], out[1], out[2]
    new_master, m2, v2 = _reference_update(
        param.astype(jnp.float32), grad.astype(jnp.float32), m, v, lr, b1,
        b2, eps, decay, step)
    return new_master.astype(param.dtype), m2, v2


def fused_adamw_update(param, grad, m, v, *, lr, step, b1, b2, eps,
                       decay, master: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  Optional[jax.Array]]:
    """(new_param, new_m, new_v, new_master|None); the XLA elementwise
    path runs where the kernel is not selected (off-TPU, unsupported
    shapes, multi-device). A selected kernel that fails to lower or
    compile raises — with the opt-in flag set, a loud error beats silently
    benchmarking the wrong path.

    ``grad`` is consumed in float32 either way (the kernel upcasts
    internally), so both paths compute identical math.
    """
    param = jnp.asarray(param)
    grad = jnp.asarray(grad)
    if usable(param.shape):
        res = _fused_call(param, grad, m, v, master,
                          _scalars(lr, step, b1, b2), float(b1), float(b2),
                          float(eps), float(decay), master is not None)
        return res[0], res[1], res[2], (res[3] if master is not None
                                        else None)
    pf = master if master is not None else param.astype(jnp.float32)
    # scalars stay in the caller's types (python floats in eager mode) so
    # the fallback is bit-identical to the pre-fusion XLA path
    new_master, m2, v2 = _reference_update(
        pf, grad.astype(jnp.float32), m, v, lr, b1, b2, eps, decay, step)
    return (new_master.astype(param.dtype), m2, v2,
            new_master if master is not None else None)
