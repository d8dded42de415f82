"""Mamba-2 (state-space duality) state update for the serving path.

The recurrence, per head ``h`` of ``heads``, channel ``p`` of ``head_dim``
and state ``n`` of ``d_state``, all in float32 — ONE decay per head per
token, and B and C shared by all heads (one group)::

    S_t[h, p, n] = exp(dt_t[h] * A[h]) * S_{t-1}[h, p, n]
                   + dt_t[h] * x_t[h, p] * B_t[n]
    y_t[h, p]    = sum_n S_t[h, p, n] * C_t[n] + D[h] * x_t[h, p]

The state is laid out ``(heads, head_dim, d_state)``: ``d_state`` (128 at the
published widths) on the lanes, so a head's state is whole vector registers.
Beside ``selective_scan.py`` (Mamba-1: a decay per (state, channel) element
and a state of ``d_state`` 16), which would take one exponential per state
element where one per head suffices.

Two entry points, one per program of the serving executor:

- :func:`ssm2_step`: ONE token for every slot (decode). Rows whose ``dt`` is
  zero keep their state bit for bit (``exp(0) = 1`` and a zero increment),
  which is how the caller masks idle and prefilling slots. The jnp
  composition, which XLA fuses into ONE pass over the state — in place
  where the state is donated, update and read-out together — under the
  scope ``ssm2_step`` in the device trace (``select.select_ssm2_step`` says
  why no hand-written kernel: one was measured, and lost).
- :func:`ssd_chunk`: a prefill chunk of one slot from a state to a state in
  the published CHUNKED (dual, matrix-product) form: within a block of
  ``chunk`` tokens the decayed lower-triangular ``C B^T`` product applied to
  ``dt * x``, plus the carried state's contribution; the new state from the
  block's decayed ``B^T (dt * x)``. Tokens past the chunk's valid length are
  given ``dt = 0`` by the caller, so the returned state is the state after
  the last valid token. Einsums that XLA puts on the matrix unit
  (``select.select_ssd_chunk`` says why), at ``highest`` precision: the
  state is float32 and stays so. Under ``ssd_chunk`` in the device trace.

``ssd_chunk`` is tested against the sequential recurrence
(``ssd_chunk_ref``), ``ssm2_step`` against the recurrence written out by hand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ references
def ssm2_step_ref(x, dt, A, Bm, Cm, D, h):
    """x (B, H, P), dt (B, H) f32; A, D (H,); Bm, Cm (B, N); h (B, H, P, N)
    f32. Returns (y (B, H, P) f32, h')."""
    dA = jnp.exp(dt * A[None])
    h = (dA[:, :, None, None] * h
         + (dt[:, :, None] * x)[..., None] * Bm[:, None, None, :])
    y = jnp.sum(h * Cm[:, None, None, :], axis=-1) + D[None, :, None] * x
    return y, h


def ssd_chunk_ref(x, dt, A, Bm, Cm, D, h0):
    """The sequential recurrence over a chunk of one slot: x (T, H, P), dt
    (T, H); Bm, Cm (T, N); h0 (H, P, N). ``lax.scan`` over T."""
    def one(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * A)[:, None, None] * h
             + (dtt[:, None] * xt)[..., None] * bt[None, None, :])
        return h, jnp.sum(h * ct[None, None, :], axis=-1) + D[:, None] * xt

    hT, y = jax.lax.scan(one, h0, (x, dt, Bm, Cm))
    return y, hT


# ----------------------------------------------------------------- chunked form
def _ssd_block(x, dt, A, Bm, Cm, h0):
    """One block of the chunked form (without the ``D * x`` skip): x (T, H,
    P), dt (T, H), Bm, Cm (T, N), h0 (H, P, N) -> (y (T, H, P), hT)."""
    T = x.shape[0]
    cum = jnp.cumsum((dt * A[None]).T, axis=1)            # (H, T), <= 0
    u = dt[:, :, None] * x                                # (T, H, P)
    # the decay from token s to token t >= s: exp(cum_t - cum_s) <= 1 (the
    # exponent is masked, not the product: above the diagonal it is positive
    # and can overflow)
    tri = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    decay = jnp.exp(jnp.where(tri[None], cum[:, :, None] - cum[:, None, :],
                              -jnp.inf))                  # (H, T, S)
    g = jnp.einsum("tn,sn->ts", Cm, Bm, precision=HIGHEST)
    y = jnp.einsum("hts,shp->thp", g[None] * decay, u, precision=HIGHEST)
    # what the carried state still adds at token t
    y = y + jnp.exp(cum).T[:, :, None] * jnp.einsum(
        "tn,hpn->thp", Cm, h0, precision=HIGHEST)
    to_end = jnp.exp(cum[:, -1:] - cum).T                 # (T, H)
    hT = (jnp.exp(cum[:, -1])[:, None, None] * h0
          + jnp.einsum("shp,sn->hpn", u * to_end[:, :, None], Bm,
                       precision=HIGHEST))
    return y, hT


def ssd_chunk_dual(x, dt, A, Bm, Cm, D, h0, chunk: int = 256):
    """The chunked form over T tokens in blocks of ``chunk`` (T at most
    ``chunk``, or a multiple of it): same operands and results as
    :func:`ssd_chunk_ref`."""
    T = x.shape[0]
    if T <= chunk:
        y, hT = _ssd_block(x, dt, A, Bm, Cm, h0)
    else:
        if T % chunk:
            raise ValueError(f"{T} tokens are no multiple of the block "
                             f"{chunk}")

        def blocks(a):
            return a.reshape(T // chunk, chunk, *a.shape[1:])

        def one(h, inp):
            xb, dtb, bb, cb = inp
            y, h = _ssd_block(xb, dtb, A, bb, cb, h)
            return h, y

        hT, y = jax.lax.scan(one, h0, tuple(blocks(a)
                                            for a in (x, dt, Bm, Cm)))
        y = y.reshape(x.shape)
    return y + D[None, :, None] * x, hT


# ---------------------------------------------------------------- entry points
def ssm2_step(x, dt, A, Bm, Cm, D, h):
    """Decode: one token for every slot; see the module docstring."""
    from .select import record, select_ssm2_step

    record("ssm2_step", select_ssm2_step(h.shape))
    with jax.named_scope("ssm2_step"):
        return ssm2_step_ref(x, dt, A, Bm, Cm, D, h)


def ssd_chunk(x, dt, A, Bm, Cm, D, h0, chunk: int = 256):
    """Prefill: one chunk of one slot, from a state to a state; see the
    module docstring."""
    from .select import record, select_ssd_chunk

    record("ssd_chunk", select_ssd_chunk(h0.shape, x.shape[0]))
    with jax.named_scope("ssd_chunk"):
        return ssd_chunk_dual(x, dt, A, Bm, Cm, D, h0, chunk)
