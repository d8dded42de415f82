"""Grouped matrix product: ``out[rows of group g] = x[rows of group g] @
w[g]`` for rows sorted by group — the expert layer's product over the held
experts that got a token (incubate/distributed/models/moe/held_experts.py).

x (M, K) with the rows of group 0 first, then group 1, ...; w (G, K, N);
``group_sizes`` int32 (G,), whose sum may be less than M: the rows after the
last group belong to no group, cost nothing and come back undefined (the
caller masks them). Work follows the rows that are in a group, in whole row
tiles — never a dense product of every row with every group's matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# megablox tiles (rows, K, N): 128 rows is the kernel's least; K and N
# tiles of 1024 keep a bf16 weight tile at 2 MB, double-buffered in VMEM
_TILING = (128, 1024, 1024)


def grouped_matmul(x, w, group_sizes, out_dtype=jnp.float32, impl=None):
    """``impl`` None asks ``select.select_grouped_matmul``."""
    from .select import XLA, record, select_grouped_matmul

    if impl is None:
        impl = record("expert_gmm", select_grouped_matmul(x.shape, w.shape))
    if impl == XLA:
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                                  preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    tm = _TILING[0]
    tk, tn = (min(t, d) for t, d in zip(_TILING[1:], w.shape[1:]))
    m = x.shape[0]
    if m % tm:      # whole row tiles: the rows added belong to no group
        x = jnp.pad(x, ((0, -m % tm), (0, 0)))
    return gmm(x, w, group_sizes.astype(jnp.int32),
               preferred_element_type=out_dtype, tiling=(tm, tk, tn))[:m]
