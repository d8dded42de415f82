"""Per-layer kernel geometry: tunable schedules for the per-op Pallas
kernels, plus the per-(op, dtype, shape, chip) winner cache.

The per-layer kernels (paged attention fp/int8, fused LoRA, flash
attention, fused norm, fused CE) take their schedule as data: one
frozen dataclass per op family, with ``validate()`` + a VMEM-occupancy
model.

The geometry contract is strict: every swept geometry is a schedule
change only — tile/block shapes, q-row tiling, hoisted-but-exact casts
— never a math-order change, so any candidate's output is BIT-EXACT
against the default geometry's (the parity sweep in
tests/test_kernel_geometry.py pins this bitwise, fp and int8). Zero values mean "derive the choice from the shapes". Knobs
that regroup floating-point accumulation (the flash kernel's kv block
and the paged kernel's blocks per group both set the online-softmax
update granularity) exist as declared axes, are honored when set
explicitly, and are excluded from the sweep candidate space; the search additionally hard-rejects
any candidate whose output is not bitwise equal to the default's, so a
non-exact schedule can never become a cached winner.

Winners are cached per ``(op, dtype, head_dim_or_row, device_kind)`` in
a :class:`GeometryCache` — the schedule space is hardware-generation-
specific (TVM / the XLA fusion study, PAPERS.md), so a fleet on mixed
TPU generations resolves per-chip winners from one artifact. The cache
persists inside ``TunedProfile`` (since schema v3) and carries its own
fingerprint; a hand-edited cache fails at load, same contract as the
profile's ``config_fingerprint``.

Resolution mirrors the kernel-mode contract (``ops.set_kernel_mode``):
``install_geometry_cache`` pins a process-wide cache that the op
dispatch seams read at TRACE time; ``GenerationServer`` installs the
profile's cache in its constructor (before the executor traces) and
records the resolved per-op geometry in its snapshot fingerprint.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Mapping, Optional, Tuple

#: int8 dequant placements for the paged-attention kernel. Both apply
#: the k/v scales in the reference order (bit-exact); they differ only
#: in WHERE the exact int8->fp cast of a fetched group of KV blocks
#: sits: "scores" casts each kv head's slice where its matmul consumes
#: it, "early" casts the whole group tile once, as soon as its copies
#: have landed (the all-heads body consumes the tile whole, so there
#: the two coincide).
PA_DEQUANT_MODES = ("scores", "early")

#: fused-LoRA accumulation layouts: which matmul chain issues first.
#: The final combine is ``y + d * s`` either way (bit-exact);
#: "delta_first" starts the low-rank chain before the base projection
#: so the small matmuls hide under the big one's MXU occupancy.
LORA_ACCUM_LAYOUTS = ("base_first", "delta_first")

#: per-core VMEM budget every occupancy model here is checked against
#: (Mosaic's scoped-VMEM limit on the v5e)
VMEM_PER_CORE_BYTES = 16 << 20


def _largest_divisor(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is <= ``want`` (>= 1). Geometry
    values quantize onto real shapes through this — a requested tile
    that doesn't divide the axis degrades deterministically instead of
    erroring, same spirit as flash's ``_pick_block``."""
    want = max(1, min(int(want), int(n)))
    for c in range(want, 0, -1):
        if n % c == 0:
            return c
    return 1


@dataclasses.dataclass(frozen=True)
class PagedAttentionGeometry:
    """Schedule of the paged decode/verify/prefill attention kernel
    (ops/paged_attention_pallas.py), fp and int8.

    ``kv_block_depth``: KV-pool blocks per group — fetched together
    and consumed by ONE online-softmax update. 0 (the default) derives
    it from the shapes (``paged_attention_pallas.group_plan``: the row
    count, block size, kv heads, head_dim, pool dtype and the VMEM
    block budget); > 0 requests that many, clamped to the table width.
    CAUTION: like flash's ``block_kv`` this sets the online-softmax
    update granularity, so two depths agree to ~1e-6, not bitwise — it
    is honored when set explicitly (tests, ``tools/kernel_bench.py``)
    and excluded from the sweep candidates.

    ``q_rows``: q-row tile. 0 = the whole W*rep GQA row group per
    program; > 0 tiles the rows across an extra parallel grid axis
    (rows are independent in attention — bit-exact). Clamped to a
    divisor of W*rep.

    ``dequant``: int8 cast placement, one of :data:`PA_DEQUANT_MODES`;
    dead (canonicalized to "scores") for fp pools.
    """

    kv_block_depth: int = 0
    q_rows: int = 0
    dequant: str = "scores"

    def validate(self) -> None:
        if not 0 <= self.kv_block_depth <= 64:
            raise ValueError("kv_block_depth must be in [0, 64], got "
                             f"{self.kv_block_depth}")
        if self.q_rows < 0:
            raise ValueError(f"q_rows must be >= 0, got {self.q_rows}")
        if self.dequant not in PA_DEQUANT_MODES:
            raise ValueError(f"dequant must be one of {PA_DEQUANT_MODES}, "
                             f"got {self.dequant!r}")

    def vmem_bytes(self, *, head_dim: int, block_size: int, window: int,
                   rep: int, kv_heads: int = 8, dtype_bytes: int = 4,
                   quantized: bool = False) -> int:
        """Worst-case VMEM residency of one program: the q tile, the two
        slots of a K and a V group (whole blocks, every kv head), and
        the online-softmax scratch."""
        rows = window * rep if self.q_rows == 0 \
            else min(self.q_rows, window * rep)
        d = self.kv_block_depth or 8
        kv_item = 1 if quantized else dtype_bytes
        group = d * block_size * kv_heads * head_dim
        n = kv_heads * rows * head_dim * dtype_bytes       # q tile
        n += 2 * 2 * group * kv_item                       # k/v slots
        if quantized and self.dequant == "early":
            n += 2 * group * dtype_bytes       # fp twins of the group tile
        n += kv_heads * rows * (2 * 128 + head_dim) * 4    # m/l/acc scratch
        return n

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class LoRAGeometry:
    """Schedule of the fused base+LoRA projection
    (ops/paged_attention_pallas.fused_lora_matmul).

    ``rank_pad``: pad the adapter rank dim up to a multiple of this
    before the kernel (0 = no padding, today's layout). Zero columns
    of A / zero rows of B contribute exact zeros to the low-rank
    chain — bit-exact — while aligning the contraction to the MXU's
    native tiling.

    ``accum``: matmul issue order, one of :data:`LORA_ACCUM_LAYOUTS`.
    """

    rank_pad: int = 0
    accum: str = "base_first"

    def validate(self) -> None:
        if self.rank_pad < 0 or self.rank_pad > 1024:
            raise ValueError("rank_pad must be in [0, 1024], got "
                             f"{self.rank_pad}")
        if self.accum not in LORA_ACCUM_LAYOUTS:
            raise ValueError(f"accum must be one of {LORA_ACCUM_LAYOUTS}, "
                             f"got {self.accum!r}")

    def padded_rank(self, rank: int) -> int:
        if self.rank_pad <= 0 or rank % self.rank_pad == 0:
            return rank
        return -(-rank // self.rank_pad) * self.rank_pad

    def vmem_bytes(self, *, seq: int, in_dim: int, out_dim: int, rank: int,
                   dtype_bytes: int = 4) -> int:
        rp = self.padded_rank(rank)
        n = seq * in_dim * dtype_bytes          # x row
        n += in_dim * out_dim * dtype_bytes     # base weight
        n += (in_dim * rp + rp * out_dim) * 4   # A/B factors (f32)
        n += 2 * seq * out_dim * 4              # y + delta accumulators
        return n

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class FlashAttentionGeometry:
    """Schedule of the flash attention kernels
    (ops/flash_attention.py). 0 = derive from the measured per-regime
    tables (``_block_defaults``) — today's behavior.

    ``block_q``: q-block rows. Rows are independent, so any block_q is
    mathematically identical — but bitwise equality additionally needs
    the backend's matmul to contract each row the same way at every
    tile shape (true of the MXU's fixed systolic order; host BLAS
    microkernels may regroup by tile). The sweep's bitwise gate decides
    empirically per chip: a block_q that regroups on this backend is
    parity-rejected and the default keeps the cell.

    ``block_kv``: kv-block width. CAUTION: this sets the online-softmax
    update granularity, so non-default values regroup the running
    max/sum accumulation — a schedule axis that is NOT parity-exact.
    It is declared here (and honored when set explicitly) but excluded
    from sweep candidates; the sweep's bitwise parity gate would reject
    any such candidate regardless.
    """

    block_q: int = 0
    block_kv: int = 0

    def validate(self) -> None:
        for name, v in (("block_q", self.block_q),
                        ("block_kv", self.block_kv)):
            if v < 0 or v > 4096:
                raise ValueError(f"{name} must be in [0, 4096], got {v}")
            if v and v % 8:
                raise ValueError(f"{name} must be sublane-aligned (8), "
                                 f"got {v}")

    def vmem_bytes(self, *, head_dim: int, seq_k: int,
                   dtype_bytes: int = 4) -> int:
        bq = self.block_q or 512
        bk = self.block_kv or 512
        n = bq * head_dim * dtype_bytes                 # q block
        n += 2 * min(bk, seq_k) * head_dim * dtype_bytes  # k/v blocks
        n += bq * (head_dim + 2) * 4                    # acc + m/l rows
        return n

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class NormGeometry:
    """Row tile of the fused RMS/Layer norm kernels
    (ops/fused_norm.py). ``rows`` = 0 derives today's
    ``max(min(512, rows), 8)``; > 0 requests that tile, clamped to a
    divisor of the flattened row count (rows are independent —
    bit-exact)."""

    rows: int = 0

    def validate(self) -> None:
        if self.rows < 0 or self.rows > 4096:
            raise ValueError(f"rows must be in [0, 4096], got {self.rows}")

    def vmem_bytes(self, *, width: int, dtype_bytes: int = 4) -> int:
        r = self.rows or 512
        return r * width * (dtype_bytes + 4) + width * dtype_bytes

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class CEGeometry:
    """Row sub-tile of the fused linear-cross-entropy forward
    (ops/fused_ce.py). ``rows`` = 0 keeps today's whole-chunk logits
    transient; > 0 computes the row-local quantities (logits row,
    logsumexp, label gather) in ``rows``-row sub-tiles of each scan
    chunk, shrinking the [chunk, V] f32 transient to [rows, V]. The
    loss reduction stays at whole-chunk granularity — per-row values
    are identical and the summation grouping is untouched, so any
    sub-tile is bit-exact vs the default. Clamped to a divisor of the
    effective chunk."""

    rows: int = 0

    def validate(self) -> None:
        if self.rows < 0 or self.rows > 16384:
            raise ValueError(f"rows must be in [0, 16384], got {self.rows}")

    def vmem_bytes(self, *, hidden: int, vocab: int,
                   dtype_bytes: int = 4) -> int:
        r = self.rows or 1024
        return r * vocab * 4 + r * hidden * dtype_bytes

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


#: op family -> geometry class; the op names are the cache/telemetry
#: vocabulary (``serving_kernel_geometry{op=...}``)
OP_GEOMETRY = {
    "paged_attention": PagedAttentionGeometry,
    "fused_lora": LoRAGeometry,
    "flash_attention": FlashAttentionGeometry,
    "fused_norm": NormGeometry,
    "fused_ce": CEGeometry,
}

OP_FAMILIES = tuple(sorted(OP_GEOMETRY))


def default_geometry(op: str):
    return OP_GEOMETRY[op]()


def geometry_from_dict(op: str, d: Mapping[str, Any]):
    cls = OP_GEOMETRY.get(op)
    if cls is None:
        raise ValueError(f"unknown geometry op {op!r} — must be one of "
                         f"{OP_FAMILIES}")
    known = {f.name for f in dataclasses.fields(cls)}
    extra = set(d) - known
    if extra:
        raise ValueError(f"unknown {op} geometry fields {sorted(extra)}")
    geom = cls(**dict(d))
    geom.validate()
    return geom


# ---------------------------------------------------------------- the cache
def local_device_kind() -> str:
    """The chip the process is on (``jax.devices()[0].device_kind`` —
    e.g. "TPU v5e", "cpu"); cache keys carry it so one artifact serves
    a mixed-generation fleet."""
    import jax

    return str(jax.devices()[0].device_kind)


def _key_str(op: str, dtype: str, key: int, device_kind: str) -> str:
    for part in (op, dtype, device_kind):
        if "|" in part:
            raise ValueError(f"geometry cache key part {part!r} may not "
                             f"contain '|'")
    return f"{op}|{dtype}|{int(key)}|{device_kind}"


class GeometryCache:
    """Winner table keyed by ``(op, dtype, head_dim_or_row,
    device_kind)``. A miss — including an unknown chip — resolves to
    the op's default geometry at the caller, never to a guess from
    another key. Serialization carries a content fingerprint
    (sha256[:12] of the canonical entry JSON); :meth:`from_dict`
    recomputes it, so a tampered cache fails at load exactly like a
    tampered profile config."""

    def __init__(self, entries: Optional[Dict[str, Any]] = None):
        self._entries: Dict[str, Any] = {}
        if entries:
            for kstr, geom in entries.items():
                op = kstr.split("|", 1)[0]
                if not isinstance(geom, OP_GEOMETRY.get(op, ())):
                    raise ValueError(
                        f"entry {kstr!r} holds {type(geom).__name__}, "
                        f"expected {OP_GEOMETRY[op].__name__}")
                self._entries[kstr] = geom

    def __len__(self) -> int:
        return len(self._entries)

    def __eq__(self, other) -> bool:
        return (isinstance(other, GeometryCache)
                and self._entries == other._entries)

    def put(self, op: str, dtype: str, key: int, device_kind: str,
            geometry) -> None:
        if not isinstance(geometry, OP_GEOMETRY[op]):
            raise ValueError(
                f"{op} wants {OP_GEOMETRY[op].__name__}, got "
                f"{type(geometry).__name__}")
        geometry.validate()
        self._entries[_key_str(op, dtype, key, device_kind)] = geometry

    def lookup(self, op: str, dtype: str, key: int,
               device_kind: Optional[str] = None):
        """The cached winner, or None on any miss (op never swept,
        different dtype/shape, unknown chip) — the caller falls back to
        the op's default geometry."""
        if device_kind is None:
            device_kind = local_device_kind()
        return self._entries.get(_key_str(op, dtype, key, device_kind))

    def entries(self) -> Dict[str, Any]:
        return dict(self._entries)

    # ------------------------------------------------------------ (de)ser
    def _canonical_entries(self) -> Dict[str, dict]:
        return {k: self._entries[k].asdict() for k in sorted(self._entries)}

    def fingerprint(self) -> str:
        blob = json.dumps(self._canonical_entries(), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()[:12]

    def to_dict(self) -> Dict[str, Any]:
        return {"entries": self._canonical_entries(),
                "fingerprint": self.fingerprint()}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any],
                  verify: bool = True) -> "GeometryCache":
        entries = {}
        for kstr, gd in dict(d.get("entries", {})).items():
            parts = kstr.split("|")
            if len(parts) != 4:
                raise ValueError(f"malformed geometry cache key {kstr!r} "
                                 f"(want op|dtype|key|device_kind)")
            entries[kstr] = geometry_from_dict(parts[0], gd)
        cache = cls(entries)
        if verify:
            fp = cache.fingerprint()
            if fp != d.get("fingerprint"):
                raise ValueError(
                    f"geometry cache fingerprint mismatch: recorded "
                    f"{d.get('fingerprint')!r}, recomputed {fp!r} — the "
                    f"cache was edited after the sweep")
        return cache


# ------------------------------------------------- trace-time resolution
# Mirrors ops.set_kernel_mode: process-wide, read at TRACE time by the
# op dispatch seams, so it must be installed before the first trace
# (GenerationServer installs its profile's cache in the constructor).
GEOMETRY_SOURCES = ("default", "profile", "swept")

_ACTIVE_CACHE: Optional[GeometryCache] = None
_ACTIVE_SOURCE: str = "default"


def install_geometry_cache(cache: Optional[GeometryCache],
                           source: str = "swept") -> None:
    """Pin the process-wide winner cache (None resets to defaults).
    ``source`` labels telemetry: "profile" when a TunedProfile carried
    it, "swept" for a cache installed directly from a sweep artifact."""
    global _ACTIVE_CACHE, _ACTIVE_SOURCE
    if cache is not None and not isinstance(cache, GeometryCache):
        raise ValueError(f"expected a GeometryCache or None, got "
                         f"{type(cache).__name__}")
    if source not in GEOMETRY_SOURCES:
        raise ValueError(f"source must be one of {GEOMETRY_SOURCES}, "
                         f"got {source!r}")
    _ACTIVE_CACHE = cache
    _ACTIVE_SOURCE = "default" if cache is None else source


def active_geometry_cache() -> Optional[GeometryCache]:
    return _ACTIVE_CACHE


def active_geometry_source() -> str:
    return _ACTIVE_SOURCE


def resolve_geometry(op: str, dtype: str, key: int,
                     device_kind: Optional[str] = None) -> Tuple[Any, str]:
    """(geometry, source) for one op at trace time: the active cache's
    winner when present, else the op's default. Never raises on a miss
    — an unknown chip degrades to the default schedule."""
    if _ACTIVE_CACHE is not None:
        hit = _ACTIVE_CACHE.lookup(op, str(dtype), int(key), device_kind)
        if hit is not None:
            return hit, _ACTIVE_SOURCE
    return default_geometry(op), "default"


def resolve_server_geometries(*, head_dim: int, hidden: int, dtype: str,
                              kv_quant: str, lora_rank: Optional[int] = None,
                              device_kind: Optional[str] = None
                              ) -> Dict[str, Tuple[Any, str]]:
    """The per-op resolution a GenerationServer performs at
    construction. Keys follow the cache convention:
    head_dim for the attention ops, the adapter rank for fused LoRA,
    the hidden width for the row-tiled fused ops; the paged-attention
    dtype is "int8" under KV quantization (the int8 kernel is a
    different schedule space than the fp one)."""
    pa_dtype = "int8" if kv_quant == "int8" else dtype
    out = {
        "paged_attention": resolve_geometry(
            "paged_attention", pa_dtype, head_dim, device_kind),
        "flash_attention": resolve_geometry(
            "flash_attention", dtype, head_dim, device_kind),
        "fused_norm": resolve_geometry(
            "fused_norm", dtype, hidden, device_kind),
        "fused_ce": resolve_geometry(
            "fused_ce", dtype, hidden, device_kind),
    }
    if lora_rank is not None:
        out["fused_lora"] = resolve_geometry(
            "fused_lora", dtype, lora_rank, device_kind)
    return out


# ------------------------------------------------------ sweep candidates
def geometry_candidates(op: str, *, quantized: bool = False,
                        vmem_limit_bytes: Optional[int] = None,
                        **shape) -> list:
    """The deterministic candidate rung for one op family: a canonical
    enumeration of the bit-exact schedule axes, deduped after
    canonicalization (fp pins the dead dequant knob), filtered by the
    op's VMEM-occupancy model against the per-core budget. Ordered so
    index 0 is always the default geometry — ties in the sweep resolve
    toward it."""
    if vmem_limit_bytes is None:
        vmem_limit_bytes = VMEM_PER_CORE_BYTES
    cands: list = []
    if op == "paged_attention":
        # kv_block_depth stays derived: it regroups the online softmax
        # (not parity-exact) — see PagedAttentionGeometry
        for q_rows in (0, 8, 16):
            for deq in (PA_DEQUANT_MODES if quantized else ("scores",)):
                cands.append(PagedAttentionGeometry(q_rows=q_rows,
                                                    dequant=deq))
        cands = [g for g in cands if g.vmem_bytes(
            head_dim=shape.get("head_dim", 128),
            block_size=shape.get("block_size", 16),
            window=shape.get("window", 4),
            rep=shape.get("rep", 4),
            quantized=quantized) <= vmem_limit_bytes]
    elif op == "fused_lora":
        for pad in (0, 8, 16, 128):
            for accum in LORA_ACCUM_LAYOUTS:
                cands.append(LoRAGeometry(rank_pad=pad, accum=accum))
        cands = [g for g in cands if g.vmem_bytes(
            seq=shape.get("seq", 1),
            in_dim=shape.get("in_dim", 1024),
            out_dim=shape.get("out_dim", 1024),
            rank=shape.get("rank", 8)) <= vmem_limit_bytes]
    elif op == "flash_attention":
        # block_kv stays at the regime default: it regroups the online
        # softmax (not parity-exact) — see FlashAttentionGeometry
        for bq in (0, 128, 256, 512):
            cands.append(FlashAttentionGeometry(block_q=bq))
        cands = [g for g in cands if g.vmem_bytes(
            head_dim=shape.get("head_dim", 128),
            seq_k=shape.get("seq_k", 2048)) <= vmem_limit_bytes]
    elif op == "fused_norm":
        for rows in (0, 8, 64, 256, 512):
            cands.append(NormGeometry(rows=rows))
    elif op == "fused_ce":
        for rows in (0, 64, 128, 256, 512):
            cands.append(CEGeometry(rows=rows))
    else:
        raise ValueError(f"unknown geometry op {op!r}")
    default = default_geometry(op)
    rest = sorted((g for g in cands if g != default),
                  key=lambda g: json.dumps(g.asdict(), sort_keys=True))
    return [default] + rest
