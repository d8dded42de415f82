"""Whole-tick decode megakernel: every transformer layer of one decode (or
speculative-verify) tick as ONE persistent Pallas program.

The per-layer kernels (paged_attention_pallas.py) already fuse attention
over the paged pool, but a tick is still N separately-launched XLA
programs and the residual stream makes 2N HBM round trips per trip. MPK
(PAPERS.md) shows the fix: fuse ACROSS layers into one persistent kernel.
This module is that kernel for the serving hot path —

- the layer schedule is the kernel's own instruction stream: the Pallas
  grid is degenerate (one program instance) and the layer loop unrolls
  inside the kernel body, because the paged KV pools must stay the
  executor's flat list of per-layer, separately-donatable HBM buffers
  (stacking them into one grid-indexable array would copy the whole KV
  cache every trip). The double-buffered DMA pipeline below does by hand
  what a grid's automatic pipelining would otherwise do;
- activations (residual stream, q/k/v, attention context) live in VMEM
  scratch across ALL layers — the residual never touches HBM mid-tick;
- per-layer weights stay in HBM (``memory_space=ANY``) and stream
  HBM→VMEM with ``prefetch_depth``-deep double buffering, one chunk per
  layer (FFN weights optionally tiled along the intermediate dim by
  ``ffn_tile`` so a layer's MLP weights never need to fit VMEM at once);
- paged KV lookups walk the block table exactly like the per-layer
  kernel: the (B, M) table rides in SMEM and each context block is a
  manual double-buffered DMA ``pool.at[tbl[b, m]] → VMEM tile``, the
  scalar-prefetch idiom without a grid;
- the int8 KV path DMAs the code pool + per-(block, kv-head) scales and
  dequantizes on the VMEM tile (``dequant="scores"`` mirrors the
  reference order: k-scale on the fp32 QK accumulator, v-scale folded
  into the probabilities); KV WRITES reproduce ``_insert_token_q``'s
  whole-block requantization in-kernel (read block → insert token →
  absmax → re-code → write back);
- the fused LoRA BGMV delta is applied per batch row right after each
  base projection, factors streamed per layer like the weights.

Numerics mirror ``ops/paged_attention.py`` / ``models/llama.py`` closely
enough that greedy decode tokens are IDENTICAL to ``kernels="reference"``
(the online softmax is ~1e-6 off the two-pass reference, same as the
per-layer kernel); tests/test_megakernel.py pins token identity for
fp/int8/±LoRA/±spec.

Geometry (tile sizes, prefetch depth, dequant placement) is DATA — a
:class:`MegakernelGeometry` the autotuner can search (autotune/space.py
registers the knobs with VMEM-budget validity arithmetic).

Selection is explicit and eager: ``set_kernel_mode("megakernel")`` plus
:func:`megakernel_supported` at executor construction (every deciding
shape is static there) route decode and spec-verify through
:func:`decode_tick`; a model the guard rejects compiles the per-layer
programs instead, with the reason recorded. Once selected, a kernel that
fails to lower or compile raises.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_QEPS = 1e-8   # scale floor — must match paged_attention._QEPS exactly

# canonical stream order for LoRA targets inside the kernel (subset used
# follows the adapter pool's configured targets)
LORA_TARGETS = ("q", "k", "v", "o", "gate", "up", "down")

DEQUANT_MODES = ("scores", "tile")


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _lanes(x):
    """(rows,) → (rows, 128): keep running max/sum scratch in a TPU-native
    lanes-broadcast layout (same idiom as the per-layer kernel)."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], 128))


# --------------------------------------------------------------- geometry
@dataclasses.dataclass(frozen=True)
class MegakernelGeometry:
    """The megakernel's tunable schedule, expressed as data.

    ``ffn_tile``: tile width along the FFN intermediate dim — 0 streams
    each layer's full gate/up/down weights as one chunk (default; keeps
    the down-projection contraction order identical to the reference),
    >0 streams ``ffn_tile``-wide column/row tiles and accumulates the
    down-projection partials in fp32 (bounds VMEM for big MLPs; not
    combinable with LoRA — the delta needs the full intermediate dim).

    ``prefetch_depth``: weight-stream lookahead in chunks (VMEM buffers
    per stream). 1 = no overlap, 2 = classic double buffering.

    ``dequant``: where the int8 KV scales land — ``"scores"`` applies
    k-scale to the fp32 QK accumulator and folds v-scale into the
    probabilities (the reference/per-layer-kernel order, token-exact vs
    ``kernels="reference"``), ``"tile"`` dequantizes the whole VMEM tile
    before the matmuls (one multiply per element, different rounding —
    NOT token-pinned).
    """

    ffn_tile: int = 0
    prefetch_depth: int = 2
    dequant: str = "scores"

    def validate(self) -> None:
        if self.ffn_tile < 0:
            raise ValueError(f"ffn_tile must be >= 0, got {self.ffn_tile}")
        if not 1 <= self.prefetch_depth <= 8:
            raise ValueError("prefetch_depth must be in [1, 8], got "
                             f"{self.prefetch_depth}")
        if self.dequant not in DEQUANT_MODES:
            raise ValueError(f"dequant must be one of {DEQUANT_MODES}, "
                             f"got {self.dequant!r}")

    def vmem_bytes(self, *, hidden: int, heads: int, kv_heads: int,
                   head_dim: int, intermediate: int, layers: int,
                   batch: int, window: int, block_size: int,
                   dtype_bytes: int = 4, quantized: bool = False) -> int:
        """Worst-case VMEM residency of the kernel's scratch + VMEM
        inputs — the validity arithmetic the autotuner's ConfigSpace
        checks against the per-core VMEM budget."""
        BW = batch * window
        Hq = heads * head_dim
        KVD = kv_heads * head_dim
        T = self.ffn_tile or intermediate
        d = self.prefetch_depth
        rep = max(heads // max(kv_heads, 1), 1)
        rows = kv_heads * window * rep
        n = 0
        # VMEM inputs: x, cos, sin (f32), per-layer norm weights
        n += BW * hidden * dtype_bytes + 2 * BW * (head_dim // 2) * 4
        n += 2 * layers * hidden * dtype_bytes
        # activation scratch (xres, xn, qs, kls, vls, ao, mlp_acc f32)
        n += BW * (2 * hidden + 2 * Hq + 2 * KVD) * dtype_bytes
        n += BW * hidden * 4
        # weight stream buffers
        n += d * (hidden * Hq + 2 * hidden * KVD + Hq * hidden
                  + 2 * hidden * T + T * hidden) * dtype_bytes
        # KV read tiles (+ scales) and write staging
        kv_item = 1 if quantized else dtype_bytes
        n += 2 * 2 * block_size * kv_heads * head_dim * kv_item
        n += 2 * kv_heads * head_dim * dtype_bytes
        if quantized:
            n += 2 * 2 * kv_heads * 4
            n += block_size * kv_heads * head_dim + kv_heads * 4
        # online-softmax scratch
        n += rows * (2 * 128 + head_dim) * 4
        return n

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


# ----------------------------------------------------------- shape guards
def _tick_shape_reason(*, D: int, bs: int, Hd: int, Hq: int, KVD: int,
                       I: int, T: int) -> Optional[str]:
    """Mosaic alignment on real hardware (interpret mode takes any shape):
    None when the shapes lower, else the reason they do not."""
    if _interpret():
        return None
    if D % 128 != 0:
        return f"head_dim {D} not lane-aligned (128)"
    if bs % 8 != 0:
        return f"block_size {bs} not sublane-aligned (8)"
    for name, dim in (("hidden", Hd), ("q_width", Hq), ("kv_width", KVD),
                      ("intermediate", I), ("ffn_tile", T)):
        if dim % 128 != 0:
            return f"{name} dim {dim} not lane-aligned (128)"
    return None


def megakernel_supported(model, cfg, *, tp: int = 1, cp: int = 1,
                         block_size: int = 16,
                         geometry: Optional[MegakernelGeometry] = None,
                         lora: bool = False) -> Optional[str]:
    """Structural/shape guard for the whole-tick kernel, checked EAGERLY
    at executor construction (all shapes are static there). Returns None
    when the megakernel can serve this model, else a human-readable
    reason — the executor records it and jits the per-layer programs
    instead (megakernel → pallas → reference, no error)."""
    geometry = geometry or MegakernelGeometry()
    geometry.validate()
    if tp > 1 or cp > 1:
        return f"multi-chip serving (tp={tp}, cp={cp}) keeps the " \
               "per-layer programs — GSPMD shards those"
    if getattr(cfg, "moe_num_experts", 0) > 0:
        return "MoE FFN layers route per token; the megakernel streams " \
               "dense gate/up/down weights"
    try:
        layers = model.model.layers
    except AttributeError:
        return "model is not LlamaForCausalLM-shaped"
    from ..nn.layer.common import Linear

    for i, layer in enumerate(layers):
        attn = layer.self_attn
        if getattr(attn, "_w8_split", None):
            return f"layer {i}: weight-only int8 attention " \
                   "(quantize_int8) is served by the per-layer path"
        mlp = layer.mlp
        for pname in ("gate_proj", "up_proj", "down_proj"):
            if type(getattr(mlp, pname, None)) is not Linear:
                return f"layer {i}: {pname} is not a plain Linear " \
                       "(weight-only int8 or LoRA-wrapped MLP)"
        for pname in ("q_proj", "k_proj", "v_proj", "o_proj"):
            if type(getattr(attn, pname, None)) is not Linear:
                return f"layer {i}: {pname} is not a plain Linear"
    I = cfg.intermediate_size
    if geometry.ffn_tile:
        if I % geometry.ffn_tile != 0:
            return f"ffn_tile {geometry.ffn_tile} does not divide " \
                   f"intermediate_size {I}"
        if lora:
            return "ffn_tile > 0 is incompatible with pooled LoRA (the " \
                   "gate/up/down delta needs the full intermediate dim)"
    D = cfg.hidden_size // cfg.num_attention_heads
    if D * cfg.num_attention_heads != cfg.hidden_size:
        return "hidden_size is not num_attention_heads * head_dim"
    if D % 2:
        return f"head_dim {D} is odd — rope splits it in half"
    return _tick_shape_reason(D=D, bs=block_size, Hd=cfg.hidden_size,
                              Hq=cfg.num_attention_heads * D,
                              KVD=cfg.num_key_value_heads * D, I=I,
                              T=geometry.ffn_tile or I)


# ------------------------------------------------------- weight stacking
def stack_layer_weights(model):
    """One-time (L, in, out) stacking of the per-layer projection weights
    plus (L, hidden) norm weights — the HBM arrays the kernel streams.
    This DOUBLES the megakernel-served model's weight HBM (the per-layer
    params stay alive for prefill); the tradeoff is one contiguous
    stream-friendly layout per projection. Built once at executor init."""
    layers = model.model.layers

    def stk(get):
        return jnp.stack([jnp.asarray(get(l)) for l in layers])

    return {
        "wq": stk(lambda l: l.self_attn.q_proj.weight.value),
        "wk": stk(lambda l: l.self_attn.k_proj.weight.value),
        "wv": stk(lambda l: l.self_attn.v_proj.weight.value),
        "wo": stk(lambda l: l.self_attn.o_proj.weight.value),
        "wg": stk(lambda l: l.mlp.gate_proj.weight.value),
        "wu": stk(lambda l: l.mlp.up_proj.weight.value),
        "wd": stk(lambda l: l.mlp.down_proj.weight.value),
        "ln1": stk(lambda l: l.input_layernorm.weight.value),
        "ln2": stk(lambda l: l.post_attention_layernorm.weight.value),
    }


def stack_lora(lora):
    """Per-layer gathered factor dicts (AdapterPool.gather_rows) →
    per-target (L, B, in, R)/(L, B, R, out) stacks + the shared (B,)
    scale, the layout the kernel streams per layer. None passes through
    (LoRA off compiles the no-factor program)."""
    if lora is None:
        return None
    targets = tuple(t for t in LORA_TARGETS if t in lora[0])
    stacked = {}
    for t in targets:
        stacked[t] = (jnp.stack([ld[t][0] for ld in lora]),
                      jnp.stack([ld[t][1] for ld in lora]))
    scale = lora[0][targets[0]][2]
    return stacked, scale


def gather_rope_rows(cos, sin, pos, W: int):
    """Pre-gather the (B, W, D/2) rope rows for window positions
    ``clip(pos + arange(W), 0, len-1)`` — layer-invariant, so gathered
    once per tick outside the kernel (matches _apply_rope_window; the
    clamp is a no-op for in-range decode positions)."""
    idx = jnp.clip(pos[:, None] + jnp.arange(W)[None, :], 0,
                   cos.shape[0] - 1)
    return jnp.take(cos, idx, axis=0), jnp.take(sin, idx, axis=0)


# -------------------------------------------------------- HBM accounting
def hbm_bytes_per_trip(cfg, *, batch: int, window: int, block_size: int,
                       avg_ctx_blocks: int, kv_quant: str = "none",
                       megakernel: bool = True,
                       dtype_bytes: int = 4) -> int:
    """Per-trip HBM byte estimate for the bench row: weight stream (all
    layers once) + KV block reads/writes + (per-layer path only) the 2L
    residual-stream round trips the megakernel eliminates."""
    L = cfg.num_hidden_layers
    Hd = cfg.hidden_size
    D = Hd // cfg.num_attention_heads
    Hq = cfg.num_attention_heads * D
    KVD = cfg.num_key_value_heads * D
    I = cfg.intermediate_size
    BW = batch * window
    w = L * (Hd * Hq + 2 * Hd * KVD + Hq * Hd + 3 * Hd * I) * dtype_bytes
    kv_item = 1 if kv_quant == "int8" else dtype_bytes
    blk = block_size * cfg.num_key_value_heads * D * kv_item
    if kv_quant == "int8":
        blk += cfg.num_key_value_heads * 4
    kv = L * batch * (2 * avg_ctx_blocks * blk          # context reads
                      + 2 * window * (2 if kv_quant == "int8" else 1) * blk)
    n = w + kv
    if not megakernel:
        n += 2 * L * BW * Hd * dtype_bytes              # residual round trips
    return int(n)


# ------------------------------------------------------------ DMA stream
class _Stream:
    """Double-buffered HBM→VMEM chunk stream: ``depth`` VMEM slots +
    dedicated DMA semaphores, chunks issued ``depth`` ahead. All chunk
    ids are trace-time Python ints, so the schedule fully unrolls."""

    def __init__(self, buf, sem, sem_base, depth, nchunks, src_fn):
        self.buf = buf
        self.sem = sem
        self.base = sem_base
        self.depth = depth
        self.n = nchunks
        self.src = src_fn

    def _copy(self, c):
        slot = c % self.depth
        return pltpu.make_async_copy(self.src(c), self.buf.at[slot],
                                     self.sem.at[self.base + slot])

    def start(self, c):
        if 0 <= c < self.n:
            self._copy(c).start()

    def wait(self, c):
        self._copy(c).wait()

    def prestart(self):
        for c in range(min(self.depth, self.n)):
            self.start(c)

    def slot(self, c):
        return c % self.depth


# ------------------------------------------------------------ the kernel
def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _tick_kernel(*refs, L, B, W, nH, KV, D, I, T, nT, bs, M, depth, eps,
                 quantized, dequant, lora_targets):
    nt_lora = len(lora_targets)
    lora_idx = {t: i for i, t in enumerate(lora_targets)}
    rep = nH // KV
    Wr = W * rep
    BW = B * W
    Hd = nH * D  # hidden == heads * head_dim for this model family
    Hq = nH * D
    KVD = KV * D
    D2 = D // 2
    P = (4 if quantized else 2) * L
    i = 0
    tables_ref, pos_ref = refs[i], refs[i + 1]
    i += 2
    lscale_ref = None
    if nt_lora:
        lscale_ref = refs[i]
        i += 1
    x_ref, cos_ref, sin_ref, ln1_ref, ln2_ref = refs[i:i + 5]
    i += 5
    wq_h, wk_h, wv_h, wo_h, wg_h, wu_h, wd_h = refs[i:i + 7]
    i += 7
    i += P                                   # aliased pool INPUT refs
    la_h = refs[i:i + 2 * nt_lora]
    i += 2 * nt_lora
    xo_ref = refs[i]
    i += 1
    pool_out = refs[i:i + P]                 # all pool access goes here
    i += P
    (xres, xn, qs, kls, vls, ao, mlp_acc) = refs[i:i + 7]
    i += 7
    wbufs = refs[i:i + 7]
    i += 7
    kblk, vblk = refs[i], refs[i + 1]
    i += 2
    kscl = vscl = iqblk = iscl = None
    if quantized:
        kscl, vscl, iqblk, iscl = refs[i:i + 4]
        i += 4
    ktok, vtok = refs[i], refs[i + 1]
    i += 2
    macc, lacc, oacc = refs[i:i + 3]
    i += 3
    lbufs = refs[i:i + 2 * nt_lora]
    i += 2 * nt_lora
    wsem = refs[i]
    i += 1
    rsem = refs[i]
    i += 1
    iosem = None
    if quantized:
        iosem = refs[i]
        i += 1
    lsem = refs[i] if nt_lora else None

    dtype = x_ref.dtype

    # ---- weight streams: attention kinds chunk per layer, FFN kinds
    # chunk per (layer, tile)
    def attn_src(h):
        return lambda c: h.at[c]

    def col_tile_src(h):
        return lambda c: h.at[c // nT, :, pl.ds((c % nT) * T, T)]

    def row_tile_src(h):
        return lambda c: h.at[c // nT, pl.ds((c % nT) * T, T), :]

    streams = {
        "wq": _Stream(wbufs[0], wsem, 0 * depth, depth, L, attn_src(wq_h)),
        "wk": _Stream(wbufs[1], wsem, 1 * depth, depth, L, attn_src(wk_h)),
        "wv": _Stream(wbufs[2], wsem, 2 * depth, depth, L, attn_src(wv_h)),
        "wo": _Stream(wbufs[3], wsem, 3 * depth, depth, L, attn_src(wo_h)),
        "wg": _Stream(wbufs[4], wsem, 4 * depth, depth, L * nT,
                      col_tile_src(wg_h)),
        "wu": _Stream(wbufs[5], wsem, 5 * depth, depth, L * nT,
                      col_tile_src(wu_h)),
        "wd": _Stream(wbufs[6], wsem, 6 * depth, depth, L * nT,
                      row_tile_src(wd_h)),
    }
    lstreams = []
    for t in range(nt_lora):
        lstreams.append((
            _Stream(lbufs[2 * t], lsem, (2 * t) * depth, depth, L,
                    attn_src(la_h[2 * t])),
            _Stream(lbufs[2 * t + 1], lsem, (2 * t + 1) * depth, depth, L,
                    attn_src(la_h[2 * t + 1])),
        ))

    xres[...] = x_ref[...]
    for st in streams.values():
        st.prestart()
    for sa, sb in lstreams:
        sa.prestart()
        sb.prestart()

    def lora_delta(tname, rows_fn, l):
        """Stacked per-row BGMV delta (BW, out) for target ``tname`` —
        lora_matmul's jnp branch order: d = ((x32 @ A[b]) @ B[b]) * s[b],
        computed in fp32 and cast by the caller. ``rows_fn(b)`` yields
        that row's (W, in) fp32 projection input."""
        t = lora_idx[tname]
        sa, sb = lstreams[t]
        sa.wait(l)
        sb.wait(l)
        sl = sa.slot(l)
        deltas = []
        for b in range(B):
            d = jnp.matmul(jnp.matmul(rows_fn(b), lbufs[2 * t][sl, b]),
                           lbufs[2 * t + 1][sl, b]) * lscale_ref[b]
            deltas.append(d)
        sa.start(l + depth)
        sb.start(l + depth)
        return jnp.concatenate(deltas, axis=0)

    def xn_rows(b):
        return xn[b * W:(b + 1) * W, :].astype(jnp.float32)

    def rope_inplace(dst, heads):
        c = cos_ref[...]
        s = sin_ref[...]
        for h in range(heads):
            s1 = slice(h * D, h * D + D2)
            s2 = slice(h * D + D2, (h + 1) * D)
            x1 = dst[:, s1].astype(jnp.float32)
            x2 = dst[:, s2].astype(jnp.float32)
            dst[:, s1] = (x1 * c - x2 * s).astype(dtype)
            dst[:, s2] = (x2 * c + x1 * s).astype(dtype)

    def layer(l):
        # ---------- attention projections on the normed residual
        xn[...] = _rms(xres[...], ln1_ref[l], eps)
        for name, dst in (("wq", qs), ("wk", kls), ("wv", vls)):
            st = streams[name]
            st.wait(l)
            dst[...] = jnp.matmul(xn[...], st.buf[st.slot(l)])
            st.start(l + depth)
        for t in ("q", "k", "v"):
            if t in lora_idx:
                dst = {"q": qs, "k": kls, "v": vls}[t]
                dst[...] = dst[...] + lora_delta(t, xn_rows, l).astype(dtype)
        rope_inplace(qs, nH)
        rope_inplace(kls, KV)

        # ---------- KV write through the block table (window tokens)
        if quantized:
            kq_o, ks_o = pool_out[4 * l], pool_out[4 * l + 1]
            vq_o, vs_o = pool_out[4 * l + 2], pool_out[4 * l + 3]
        else:
            kp_o, vp_o = pool_out[2 * l], pool_out[2 * l + 1]
        for b in range(B):
            for w in range(W):
                pj = pos_ref[b] + w
                bid = tables_ref[b, pj // bs]
                off = pj % bs
                krow = kls[b * W + w, :].reshape(KV, D)
                vrow = vls[b * W + w, :].reshape(KV, D)
                if not quantized:
                    ktok[...] = krow
                    vtok[...] = vrow
                    ck = pltpu.make_async_copy(ktok, kp_o.at[bid, off],
                                               rsem.at[4])
                    cv = pltpu.make_async_copy(vtok, vp_o.at[bid, off],
                                               rsem.at[5])
                    ck.start()
                    cv.start()
                    ck.wait()
                    cv.wait()
                else:
                    # _insert_token_q in-kernel: whole-block requant
                    for tok, q_o, s_o in ((krow, kq_o, ks_o),
                                          (vrow, vq_o, vs_o)):
                        ci = pltpu.make_async_copy(q_o.at[bid], iqblk,
                                                   iosem.at[0])
                        cs = pltpu.make_async_copy(s_o.at[bid], iscl,
                                                   iosem.at[1])
                        ci.start()
                        cs.start()
                        ci.wait()
                        cs.wait()
                        blk = iqblk[...].astype(jnp.float32) * \
                            iscl[...][None, :, None]
                        blk = jax.lax.dynamic_update_slice(
                            blk, tok.astype(jnp.float32)[None],
                            (off, jnp.int32(0), jnp.int32(0)))
                        amax = jnp.max(jnp.abs(blk), axis=(0, 2))
                        ns = jnp.maximum(amax, _QEPS) / 127.0
                        iqblk[...] = jnp.clip(
                            jnp.round(blk / ns[None, :, None]), -127,
                            127).astype(jnp.int8)
                        iscl[...] = ns
                        co = pltpu.make_async_copy(iqblk, q_o.at[bid],
                                                   iosem.at[2])
                        cso = pltpu.make_async_copy(iscl, s_o.at[bid],
                                                    iosem.at[3])
                        co.start()
                        cso.start()
                        co.wait()
                        cso.wait()

        # ---------- paged attention per row (online softmax over blocks)
        if quantized:
            k_src, ks_src = pool_out[4 * l], pool_out[4 * l + 1]
            v_src, vs_src = pool_out[4 * l + 2], pool_out[4 * l + 3]
        else:
            k_src, v_src = pool_out[2 * l], pool_out[2 * l + 1]

        def start_blk(b, m, slot):
            blk_id = tables_ref[b, m]
            pltpu.make_async_copy(k_src.at[blk_id], kblk.at[slot],
                                  rsem.at[0 + slot]).start()
            pltpu.make_async_copy(v_src.at[blk_id], vblk.at[slot],
                                  rsem.at[2 + slot]).start()
            if quantized:
                pltpu.make_async_copy(ks_src.at[blk_id], kscl.at[slot],
                                      rsem.at[6 + slot]).start()
                pltpu.make_async_copy(vs_src.at[blk_id], vscl.at[slot],
                                      rsem.at[8 + slot]).start()

        def wait_blk(b, m, slot):
            blk_id = tables_ref[b, m]
            pltpu.make_async_copy(k_src.at[blk_id], kblk.at[slot],
                                  rsem.at[0 + slot]).wait()
            pltpu.make_async_copy(v_src.at[blk_id], vblk.at[slot],
                                  rsem.at[2 + slot]).wait()
            if quantized:
                pltpu.make_async_copy(ks_src.at[blk_id], kscl.at[slot],
                                      rsem.at[6 + slot]).wait()
                pltpu.make_async_copy(vs_src.at[blk_id], vscl.at[slot],
                                      rsem.at[8 + slot]).wait()

        for b in range(B):
            macc[...] = jnp.full((KV * Wr, 128), NEG_INF, jnp.float32)
            lacc[...] = jnp.zeros((KV * Wr, 128), jnp.float32)
            oacc[...] = jnp.zeros((KV * Wr, D), jnp.float32)
            nb = jnp.minimum((pos_ref[b] + (W - 1)) // bs + 1, M)
            start_blk(b, 0, 0)

            def mbody(m, _, b=b):
                slot = jax.lax.rem(m, jnp.int32(2))

                @pl.when(m + 1 < nb)
                def _():
                    start_blk(b, m + 1, jax.lax.rem(m + 1, jnp.int32(2)))

                wait_blk(b, m, slot)
                for g in range(KV):
                    qt = qs[b * W:(b + 1) * W,
                            g * rep * D:(g + 1) * rep * D].reshape(
                        W, rep, D).reshape(Wr, D)
                    kt = kblk[slot][:, g, :]
                    vt = vblk[slot][:, g, :]
                    if quantized:
                        if dequant == "tile":
                            kt = kt.astype(jnp.float32) * kscl[slot, g]
                            vt = vt.astype(jnp.float32) * vscl[slot, g]
                            kt = kt.astype(qt.dtype)
                            vt = vt.astype(qt.dtype)
                        else:
                            kt = kt.astype(qt.dtype)
                            vt = vt.astype(qt.dtype)
                    s_ = jax.lax.dot_general(
                        qt, kt, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if quantized and dequant == "scores":
                        s_ = s_ * kscl[slot, g]
                    s_ = s_ / jnp.float32(math.sqrt(D))
                    rows_i = jax.lax.broadcasted_iota(jnp.int32, s_.shape, 0)
                    cols_i = jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
                    qpos = pos_ref[b] + rows_i // rep
                    s_ = jnp.where(m * bs + cols_i <= qpos, s_, NEG_INF)
                    gsl = slice(g * Wr, (g + 1) * Wr)
                    m_prev = macc[gsl, 0]
                    l_prev = lacc[gsl, 0]
                    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1))
                    p = jnp.exp(s_ - m_new[:, None])
                    alpha = jnp.exp(m_prev - m_new)
                    lacc[gsl, :] = _lanes(l_prev * alpha
                                          + jnp.sum(p, axis=-1))
                    if quantized and dequant == "scores":
                        p = p * vscl[slot, g]
                    oacc[gsl, :] = oacc[gsl, :] * alpha[:, None] + \
                        jax.lax.dot_general(
                            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    macc[gsl, :] = _lanes(m_new)
                return 0

            jax.lax.fori_loop(0, nb, mbody, 0)
            lsafe = jnp.maximum(lacc[:, 0], 1e-30)
            outv = (oacc[...] / lsafe[:, None]).astype(dtype)
            for g in range(KV):
                ao[b * W:(b + 1) * W, g * rep * D:(g + 1) * rep * D] = \
                    outv[g * Wr:(g + 1) * Wr, :].reshape(W, rep * D)

        # ---------- output projection + residual
        st = streams["wo"]
        st.wait(l)
        attn_o = jnp.matmul(ao[...], st.buf[st.slot(l)])
        st.start(l + depth)
        if "o" in lora_idx:
            # o-delta reads the ATTENTION OUTPUT rows, not xn
            attn_o = attn_o + lora_delta(
                "o", lambda b: ao[b * W:(b + 1) * W, :].astype(jnp.float32),
                l).astype(dtype)
        xres[...] = xres[...] + attn_o

        # ---------- MLP on the re-normed residual
        xn[...] = _rms(xres[...], ln2_ref[l], eps)
        sg, su, sd = streams["wg"], streams["wu"], streams["wd"]
        if nT == 1:
            sg.wait(l)
            su.wait(l)
            g_ = jnp.matmul(xn[...], sg.buf[sg.slot(l)])
            u_ = jnp.matmul(xn[...], su.buf[su.slot(l)])
            sg.start(l + depth)
            su.start(l + depth)
            if "gate" in lora_idx:
                g_ = g_ + lora_delta("gate", xn_rows, l).astype(dtype)
            if "up" in lora_idx:
                u_ = u_ + lora_delta("up", xn_rows, l).astype(dtype)
            h_ = jax.nn.silu(g_) * u_
            sd.wait(l)
            mo = jnp.matmul(h_, sd.buf[sd.slot(l)])
            sd.start(l + depth)
            if "down" in lora_idx:
                mo = mo + lora_delta(
                    "down",
                    lambda b: h_[b * W:(b + 1) * W, :].astype(jnp.float32),
                    l).astype(dtype)
            xres[...] = xres[...] + mo
        else:
            mlp_acc[...] = jnp.zeros((BW, Hd), jnp.float32)
            for t in range(nT):
                c = l * nT + t
                sg.wait(c)
                su.wait(c)
                g_ = jnp.matmul(xn[...], sg.buf[sg.slot(c)])
                u_ = jnp.matmul(xn[...], su.buf[su.slot(c)])
                sg.start(c + depth)
                su.start(c + depth)
                h_ = jax.nn.silu(g_) * u_
                sd.wait(c)
                mlp_acc[...] = mlp_acc[...] + jnp.matmul(
                    h_, sd.buf[sd.slot(c)]).astype(jnp.float32)
                sd.start(c + depth)
            xres[...] = xres[...] + mlp_acc[...].astype(dtype)

    for l in range(L):
        layer(l)
    xo_ref[...] = xres[...]


# ------------------------------------------------------------ the wrapper
def decode_tick(x, pools, tables, pos, weights, cos_rows, sin_rows, *,
                block_size: int, geometry: Optional[MegakernelGeometry]
                = None, eps: float = 1e-6, lora=None):
    """Run one whole decode/verify tick through the persistent kernel.

    ``x``: (B, W, hidden) embedded window activations; ``pools``: the
    executor's flat per-layer KV pool list (fp: 2/layer, int8: 4/layer) —
    ALIASED into the outputs, so callers treat them as donated; ``weights``
    from :func:`stack_layer_weights`; ``cos_rows``/``sin_rows``: (B, W,
    D/2) from :func:`gather_rope_rows`; ``lora`` from :func:`stack_lora`.

    Returns ``(x_out (B, W, hidden), new_pools list)`` — the tick's
    post-norm input is NOT applied here (the executor's final norm + head
    stay outside, like the per-layer path). Shapes
    :func:`megakernel_supported` would have rejected raise ``ValueError``."""
    geometry = geometry or MegakernelGeometry()
    geometry.validate()
    B, W, Hd = x.shape
    BW = B * W
    L, _, Hq = weights["wq"].shape
    KVD = weights["wk"].shape[2]
    D2 = cos_rows.shape[-1]
    D = 2 * D2
    nH = Hq // D
    KV = KVD // D
    I = weights["wg"].shape[2]
    T = geometry.ffn_tile or I
    nT = I // T
    depth = geometry.prefetch_depth
    M = tables.shape[1]
    bs = block_size
    quantized = pools[0].dtype == jnp.int8
    P = (4 if quantized else 2) * L
    assert len(pools) == P, (len(pools), P)
    reason = _tick_shape_reason(D=D, bs=bs, Hd=Hd, Hq=Hq, KVD=KVD, I=I, T=T)
    if reason is not None:
        raise ValueError(f"decode_tick: {reason}")

    dtype = x.dtype
    kv_dtype = jnp.int8 if quantized else pools[0].dtype

    lora_targets = ()
    lora_inputs = []
    lscale_in = []
    if lora is not None:
        stacked, scale = lora
        lora_targets = tuple(t for t in LORA_TARGETS if t in stacked)
        lscale_in = [jnp.asarray(scale, jnp.float32)]
        for t in lora_targets:
            a, b_ = stacked[t]
            lora_inputs += [jnp.asarray(a, jnp.float32),
                            jnp.asarray(b_, jnp.float32)]
    nt = len(lora_targets)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_ = pl.BlockSpec(memory_space=pl.ANY)

    inputs = [tables, pos, *lscale_in,
              x.reshape(BW, Hd),
              cos_rows.reshape(BW, D2).astype(jnp.float32),
              sin_rows.reshape(BW, D2).astype(jnp.float32),
              weights["ln1"], weights["ln2"],
              weights["wq"], weights["wk"], weights["wv"], weights["wo"],
              weights["wg"], weights["wu"], weights["wd"],
              *pools, *lora_inputs]
    in_specs = ([smem, smem] + [smem] * len(lscale_in) + [vmem] * 5
                + [any_] * 7 + [any_] * P + [any_] * (2 * nt))
    pool_base = 2 + len(lscale_in) + 5 + 7
    aliases = {pool_base + j: 1 + j for j in range(P)}

    out_shape = [jax.ShapeDtypeStruct((BW, Hd), dtype)] + \
        [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools]
    out_specs = [vmem] + [any_] * P

    rep = nH // KV
    Wr = W * rep
    scratch = [
        pltpu.VMEM((BW, Hd), dtype),          # xres
        pltpu.VMEM((BW, Hd), dtype),          # xn
        pltpu.VMEM((BW, Hq), dtype),          # qs
        pltpu.VMEM((BW, KVD), dtype),         # kls
        pltpu.VMEM((BW, KVD), dtype),         # vls
        pltpu.VMEM((BW, Hq), dtype),          # ao
        pltpu.VMEM((BW, Hd), jnp.float32),    # mlp_acc
        pltpu.VMEM((depth, Hd, Hq), dtype),   # wq stream
        pltpu.VMEM((depth, Hd, KVD), dtype),  # wk
        pltpu.VMEM((depth, Hd, KVD), dtype),  # wv
        pltpu.VMEM((depth, Hq, Hd), dtype),   # wo
        pltpu.VMEM((depth, Hd, T), dtype),    # wg
        pltpu.VMEM((depth, Hd, T), dtype),    # wu
        pltpu.VMEM((depth, T, Hd), dtype),    # wd
        pltpu.VMEM((2, bs, KV, D), kv_dtype),  # kblk
        pltpu.VMEM((2, bs, KV, D), kv_dtype),  # vblk
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, KV), jnp.float32),      # kscl
            pltpu.VMEM((2, KV), jnp.float32),      # vscl
            pltpu.VMEM((bs, KV, D), jnp.int8),     # iqblk (requant staging)
            pltpu.VMEM((KV,), jnp.float32),        # iscl
        ]
    scratch += [
        pltpu.VMEM((KV, D), dtype),                # ktok
        pltpu.VMEM((KV, D), dtype),                # vtok
        pltpu.VMEM((KV * Wr, 128), jnp.float32),   # macc
        pltpu.VMEM((KV * Wr, 128), jnp.float32),   # lacc
        pltpu.VMEM((KV * Wr, D), jnp.float32),     # oacc
    ]
    for t in lora_targets:
        a, b_ = lora[0][t]
        scratch += [pltpu.VMEM((depth,) + a.shape[1:], jnp.float32),
                    pltpu.VMEM((depth,) + b_.shape[1:], jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((7 * depth,)),   # wsem
                pltpu.SemaphoreType.DMA((10,))]          # rsem
    if quantized:
        scratch.append(pltpu.SemaphoreType.DMA((4,)))    # iosem
    if nt:
        scratch.append(pltpu.SemaphoreType.DMA((2 * nt * depth,)))  # lsem

    kernel = functools.partial(
        _tick_kernel, L=L, B=B, W=W, nH=nH, KV=KV, D=D, I=I, T=T, nT=nT,
        bs=bs, M=M, depth=depth, eps=eps, quantized=quantized,
        dequant=geometry.dequant, lora_targets=lora_targets)

    outs = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        interpret=_interpret(),
    )(*inputs)
    return outs[0].reshape(B, W, Hd), list(outs[1:])
