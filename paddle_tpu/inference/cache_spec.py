"""Per-layer cache spec — what each layer of a served model keeps per request.

A served model declares, layer by layer, which of six kinds of state it
holds (``model.cache_spec()``); the allocator's block size, the executor's
pools, admission, preemption and the snapshot payloads are all derived from
that one declaration instead of from "every layer owns one K and one V pool":

- ``full(kv_heads, head_dim)``: K/V of every position, in blocks of the
  shared pool that the :class:`~.paged_cache.BlockAllocator` hands out. Cost
  grows with the request's length; this is the only kind admission has to
  reckon in blocks. A block is ``(block_size, kv_heads, head_dim)``
  (``layout="token"``) or ``(kv_heads, block_size, head_dim)``
  (``layout="head"``: for a number of kv heads that is no sublane multiple,
  which a token-major block would pad in HBM); the model that declares the
  layout is the one that reads and writes the pool.
- ``latent(width)``: ONE row of ``width`` values a position — the
  compressed key/value of latent attention (MLA: the normed latent and the
  one rotated rope key all heads share), after the norm and after the
  rotation — in blocks of the SAME shared pool, handed out by the same
  allocator under the same block ids: one pool tensor a layer, ``(block_size,
  row_width)`` a block, where ``row_width`` is ``width`` rounded up to whole
  128-lane tiles (what the device's tiled layout holds either way; the pad
  lanes stay zero and :meth:`CacheSpec.block_bytes` counts them). Admission,
  preemption, swap and prefix sharing treat its blocks as a ``full`` layer's.
- ``window(n, kv_heads, head_dim)``: K/V of the last ``n`` positions only, in
  a RING of ``ceil(n / block_size) + 1`` blocks that the slot owns for as
  long as it is occupied (position ``p`` lives in ring block
  ``(p // bs) % ring``). Fixed cost per slot however long the request.
- ``shared(source)``: no cache of its own; the layer reads layer
  ``source``'s pool through the same block table.
- ``state(shapes)``: slot-indexed arrays (name, per-slot shape, dtype) that
  are overwritten every step — a recurrent layer's convolution tail and
  SSM state. Fixed cost per slot; starts from zero when a request's first
  chunk runs; travels with the request when it is preempted or captured.
- ``none()``: nothing.

A dense decoder (``LlamaForCausalLM``) is the all-``full`` case
(:func:`dense_decoder_spec`); nothing in the engine asks which class it
serves.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["LayerCache", "CacheSpec", "CacheSpecError", "full", "latent",
           "window", "shared", "state", "none", "dense_decoder_spec"]

KINDS = ("full", "latent", "window", "shared", "state", "none")
LANES = 128


class CacheSpecError(ValueError):
    """The server was asked for a feature that the model's cache spec cannot
    serve (raised at construction, never mid-request)."""


@dataclass(frozen=True)
class LayerCache:
    kind: str
    kv_heads: int = 0
    head_dim: int = 0
    window: int = 0                       # window: positions attended
    source: int = -1                      # shared: the layer that owns the pool
    shapes: Tuple[Tuple[str, Tuple[int, ...], str], ...] = ()   # state
    layout: str = "token"                 # full / window: "token" | "head"

    @property
    def row_width(self) -> int:
        """latent: the pool row as the device holds it — the width in whole
        lane tiles."""
        return -(-self.head_dim // LANES) * LANES

    def block_shape(self, block_size: int) -> Tuple[int, ...]:
        """One block of this layer's K (or V) pool; of a latent layer's one
        pool."""
        if self.kind == "latent":
            return block_size, self.row_width
        if self.layout == "head":
            return self.kv_heads, block_size, self.head_dim
        return block_size, self.kv_heads, self.head_dim


def full(kv_heads: int, head_dim: int, layout: str = "token") -> LayerCache:
    return LayerCache("full", int(kv_heads), int(head_dim), layout=layout)


def latent(width: int) -> LayerCache:
    if width < 1:
        raise ValueError(f"latent width must be >= 1, got {width}")
    return LayerCache("latent", kv_heads=1, head_dim=int(width))


def window(n: int, kv_heads: int, head_dim: int,
           layout: str = "token") -> LayerCache:
    if n < 1:
        raise ValueError(f"window must be >= 1, got {n}")
    return LayerCache("window", int(kv_heads), int(head_dim), window=int(n),
                      layout=layout)


def shared(source: int) -> LayerCache:
    return LayerCache("shared", source=int(source))


def state(shapes) -> LayerCache:
    return LayerCache("state", shapes=tuple(
        (str(n), tuple(int(d) for d in s), str(np.dtype(t)))
        for n, s, t in shapes))


def none() -> LayerCache:
    return LayerCache("none")


class CacheSpec:
    """The model's layers in order, and the KV dtype of its pools."""

    def __init__(self, layers: List[LayerCache], dtype):
        self.layers = tuple(layers)
        self.dtype = np.dtype(dtype)
        for i, l in enumerate(self.layers):
            if l.kind not in KINDS:
                raise ValueError(f"layer {i}: unknown cache kind {l.kind!r}")
            if l.layout not in ("token", "head"):
                raise ValueError(f"layer {i}: unknown layout {l.layout!r}")
            if l.kind == "shared" and (
                    not 0 <= l.source < i
                    or self.layers[l.source].kind != "full"):
                raise ValueError(f"layer {i} shares layer {l.source}, which "
                                 f"must be an earlier 'full' layer")
        self.full_layers = self.of_kind("full")
        self.latent_layers = self.of_kind("latent")
        # the layers that own blocks of the shared pool, in layer order
        self.pool_layers = [i for i, l in enumerate(self.layers)
                            if l.kind in ("full", "latent")]
        self.slot_layers = [i for i, l in enumerate(self.layers)
                            if l.kind in ("window", "state")]

    def of_kind(self, kind: str) -> List[int]:
        return [i for i, l in enumerate(self.layers) if l.kind == kind]

    @property
    def has_slot_state(self) -> bool:
        """True when a slot holds anything besides blocks of the shared
        pool: then a request cannot start from another request's blocks
        (no prefix sharing) and a preempted request carries arrays along."""
        return bool(self.slot_layers)

    def kv_geometry(self) -> Tuple[int, int]:
        """(kv_heads, head_dim) of the block pool, as the attention kernel
        sees it."""
        for l in self.layers:
            if l.kind in ("full", "window"):
                return l.kv_heads, l.head_dim
            if l.kind == "latent":
                return 1, l.row_width
        return 0, 0

    # ------------------------------------------------------------------ bytes
    def latent_block_bytes(self, block_size: int) -> int:
        """The ``latent`` layers' part of :meth:`block_bytes`: one row of
        ``row_width`` values a token a layer, as the device holds it."""
        return sum(block_size * self.layers[i].row_width
                   * self.dtype.itemsize for i in self.latent_layers)

    def block_bytes(self, block_size: int, kv_quant: str = "none") -> int:
        """Bytes of ONE block of the shared pool over all ``full`` layers
        (K + V; int8 codes carry one f32 scale per block and kv head) and
        all ``latent`` layers (one row a token)."""
        n = self.latent_block_bytes(block_size)
        for i in self.full_layers:
            l = self.layers[i]
            if kv_quant == "int8":
                per = block_size * l.kv_heads * l.head_dim + l.kv_heads * 4
            else:
                per = (block_size * l.kv_heads * l.head_dim
                       * self.dtype.itemsize)
            n += 2 * per
        return n

    def ring_blocks(self, layer: int, block_size: int) -> int:
        """Blocks of a window layer's ring: the window plus the block being
        written."""
        return -(-self.layers[layer].window // block_size) + 1

    def slot_bytes(self, block_size: int) -> Dict[str, int]:
        """Bytes a slot owns for as long as it is occupied, by kind."""
        out = {"window": 0, "state": 0}
        for i in self.slot_layers:
            l = self.layers[i]
            if l.kind == "window":
                out["window"] += (2 * self.ring_blocks(i, block_size)
                                  * block_size * l.kv_heads * l.head_dim
                                  * self.dtype.itemsize)
            else:
                out["state"] += sum(int(np.prod(s)) * np.dtype(t).itemsize
                                    for _, s, t in l.shapes)
        return out


def dense_decoder_spec(cfg) -> CacheSpec:
    """Every layer ``full``: a dense GQA decoder with ``head_dim =
    hidden / heads`` (the config classes of models/llama.py)."""
    from ..framework.dtype import convert_dtype

    import jax.numpy as jnp

    d = cfg.hidden_size // cfg.num_attention_heads
    dtype = jnp.zeros((), convert_dtype(cfg.dtype)).dtype
    return CacheSpec([full(cfg.num_key_value_heads, d)
                      for _ in range(cfg.num_hidden_layers)], dtype)
