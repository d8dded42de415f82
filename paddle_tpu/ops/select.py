"""Kernel selection — one rule per hot op.

Whether an op runs its Pallas kernel or its jnp composition is decided
BEFORE the kernel is traced, from what the code can observe: the target
platform, the process-wide kernel mode, whether the enclosing trace is
partitioned by GSPMD over several devices, and the static shapes/dtypes.
Each rule below is a plain function of those inputs (tests call them
directly, docs/kernels.md tabulates them). Once a rule has selected a
kernel, a failure to lower or compile it is an error that reaches the
caller — no call site catches it and continues on another path.

Every rule takes ``platform`` as an argument (default: the platform this
process runs on) so the answer for a TPU can be asked from a machine that
has none — the compile-only check in tests/test_chip_bringup.py lowers the
selected kernels against a ``v5e:2x2`` topology from a CPU sandbox under
:func:`target_platform`.

Implementations are named ``"pallas"`` (compiled by Mosaic),
``"pallas-interpret"`` (the Pallas interpreter: CPU parity tests only) and
``"xla"`` (the jnp composition, compiled by XLA). :func:`record` counts
what each op traced under, so an entry point can print which
implementation every hot op took (:func:`selected`).
"""
from __future__ import annotations

import collections
import contextlib
import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np

PALLAS = "pallas"
INTERPRET = "pallas-interpret"
XLA = "xla"

KERNEL_MODES = ("auto", "pallas", "reference")

_KERNEL_MODE = "auto"
_TARGET_PLATFORM: Optional[str] = None
_SELECTED: "collections.Counter[Tuple[str, str]]" = collections.Counter()

# Mosaic's default scoped-VMEM limit on v5e is 16 MiB; row-tiled kernels
# budget half of it for their double-buffered in/out blocks and leave the
# rest to the compiler's f32 temporaries.
_VMEM_BLOCK_BUDGET = 8 * 1024 * 1024


# ------------------------------------------------------------------- mode
def set_kernel_mode(mode: str) -> None:
    """Pin the process-wide kernel dispatch: ``"pallas"`` forces the
    Pallas kernels (interpret mode off-TPU), ``"reference"`` forces the
    jnp compositions, ``"auto"`` restores platform-based selection."""
    global _KERNEL_MODE
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"kernel mode must be one of {KERNEL_MODES}, got {mode!r}")
    _KERNEL_MODE = mode


def kernel_mode() -> str:
    return _KERNEL_MODE


# --------------------------------------------------------------- platform
def current_platform() -> str:
    """The platform kernels are being selected for: the
    :func:`target_platform` override when one is active, else the backend
    this process runs on."""
    return _TARGET_PLATFORM or jax.default_backend()


@contextlib.contextmanager
def target_platform(platform: str):
    """Select (and lower) for ``platform`` instead of the local backend —
    the seam the compile-only TPU check uses from a CPU sandbox."""
    global _TARGET_PLATFORM
    prev, _TARGET_PLATFORM = _TARGET_PLATFORM, platform
    try:
        yield
    finally:
        _TARGET_PLATFORM = prev


def pallas_backend(platform: Optional[str] = None) -> Optional[str]:
    """How a Pallas kernel would run on ``platform`` under the current
    kernel mode: :data:`PALLAS`, :data:`INTERPRET`, or None (jnp only)."""
    platform = platform or current_platform()
    if _KERNEL_MODE == "reference":
        return None
    if platform == "tpu":
        return PALLAS
    if (_KERNEL_MODE == "pallas"
            or os.environ.get("PT_FLASH_INTERPRET") == "1"):
        return INTERPRET
    return None


def use_pallas(platform: Optional[str] = None) -> bool:
    return pallas_backend(platform) is not None


def pallas_interpret(platform: Optional[str] = None) -> bool:
    """Interpret mode: the Pallas path was requested on a non-TPU
    platform (no Mosaic compiler there)."""
    return pallas_backend(platform) == INTERPRET


def partitioned() -> bool:
    """True when the enclosing trace is partitioned by GSPMD over more
    than one device: a mesh context is active (``ParallelEngine``, the tp
    serving executor) and we are not inside a fully-manual ``shard_map``
    island. Mosaic kernels cannot be partitioned automatically, so under
    this condition a Pallas call must sit in an island or not be
    selected."""
    from ..parallel.api import current_mesh, in_spmd_region

    mesh = current_mesh()
    if mesh is None or mesh.size <= 1 or not in_spmd_region():
        return False
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return True
    manual = set(am.manual_axes)
    return any(n not in manual for n, s in am.shape.items() if s > 1)


# ---------------------------------------------------------------- counters
def record(op: str, impl: str) -> str:
    """Count one trace of ``op`` under ``impl``; returns ``impl``."""
    _SELECTED[(op, impl)] += 1
    return impl


def selected(reset: bool = False) -> Dict[str, Dict[str, int]]:
    """{op: {impl: traces}} since the last reset."""
    out: Dict[str, Dict[str, int]] = {}
    for (op, impl), n in sorted(_SELECTED.items()):
        out.setdefault(op, {})[impl] = n
    if reset:
        _SELECTED.clear()
    return out


# ------------------------------------------------------------------- rules
def _kernel_backend(platform, is_partitioned) -> Optional[str]:
    """:func:`pallas_backend`, or None when GSPMD partitions the trace
    (``is_partitioned`` None asks :func:`partitioned`) — the preamble every
    rule shares."""
    backend = pallas_backend(platform)
    if backend is None:
        return None
    if partitioned() if is_partitioned is None else is_partitioned:
        return None
    return backend


def _sublane(dtype) -> int:
    """Minimum second-minor tile for ``dtype`` (f32 8, bf16 16, int8 32)."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def select_flash_attention(q_shape, k_shape, *, platform=None,
                           is_partitioned=None) -> str:
    """(B, H, S, D) flash attention. Pallas when kernels are on, the trace
    is not GSPMD-partitioned (callers that can, open a shard_map island
    first — models/llama.py) and both sequence lengths tile by
    ``min(128, S)``. Mosaic additionally needs both lengths to be
    multiples of 128 (below that the forward compiles but the backward
    kernels are refused: "cannot statically prove that index ... is a
    multiple of 128") and head_dim a multiple of 64 (64, 128, 192 and 256
    compile against the v5e target; 32 does not tile)."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    sq, sk, d = q_shape[2], k_shape[2], q_shape[3]
    if sq % min(128, sq) or sk % min(128, sk):
        return XLA
    if backend == PALLAS and (sq % 128 or sk % 128 or d % 64):
        return XLA
    return backend


def norm_block_rows(rows: int, width: int, dtype, want: int = 0) -> int:
    """Row tile of the fused norm kernels: the largest divisor of ``rows``
    that is a sublane multiple (or ``rows`` itself) and keeps the four
    double-buffered (tile, width) in/out blocks inside the VMEM block
    budget — 512 rows at width 2048 bf16, 256 at 4096 bf16, 128 at 4096
    f32. ``want`` > 0 (a swept NormGeometry) lowers the cap further.
    Returns 0 when no such tile exists."""
    itemsize = np.dtype(dtype).itemsize
    cap = min(512, _VMEM_BLOCK_BUDGET // (4 * width * itemsize))
    if want > 0:
        cap = min(cap, want)
    if rows <= cap:
        return rows
    sub = _sublane(dtype)
    for c in range(cap - cap % sub, 0, -sub):
        if rows % c == 0:
            return c
    return 0


def select_fused_norm(rows: int, width: int, dtype, *, platform=None,
                      is_partitioned=None) -> str:
    """Fused RMS/Layer norm. The kernel is TPU-only (off-TPU the jnp
    composition IS the kernel's math); not selected under a GSPMD-
    partitioned trace (XLA fuses the norm into its neighbours there), for
    a lane-unaligned width, or when no row tile fits the VMEM budget."""
    if _kernel_backend(platform, is_partitioned) != PALLAS:
        return XLA
    if width % 128 or norm_block_rows(rows, width, dtype) == 0:
        return XLA
    return PALLAS


def select_paged_attention(q_shape, pool_shape, *, head_major=False,
                           platform=None, is_partitioned=None) -> str:
    """Paged decode / verify / prefill-chunk attention, fp and int8 pools.
    q (B, W, H, D); pool (N, bs, KV, D), or (N, KV, bs, D) ``head_major``.
    Pallas when kernels are on and the trace is not partitioned (the tp
    serving executor is GSPMD: jnp there); Mosaic additionally needs a
    lane-aligned head_dim, whole sublane tiles in a block's second-minor
    dimension — the kv heads of a token-major block (a multiple of 8, or
    1, 2, 4: Mosaic refused 10 from the sandbox, PR 27), the block size of
    a head-major one (a multiple of 16: bf16 tiles) — and
    the per-program f32 accumulator (KV * W*rep * D) inside the VMEM
    block budget.

    ``head_dim`` is the width of a POOL row, not of the model's head: a
    model whose heads are 64 wide and come in pairs (differential
    attention, models/phi4flash.py) stores the pair side by side, 128
    wide, and asks this rule with those shapes — its ``(q1|0)`` and
    ``(0|q2)`` query rows then run the same kernel. Its 10 packed kv heads
    are no sublane multiple, so its pools are head-major. A 64-wide pool
    that nobody packed, or 10 kv heads token-major, go to the jnp
    composition, and ``selected()`` says so under the caller's op name
    (``paged_attention``, ``paged_attention_q``,
    ``paged_window_attention``)."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    if backend == INTERPRET:
        return backend
    _, w, h, d = q_shape
    if head_major:
        _, kv, bs, _ = pool_shape
        aligned = bs % 16 == 0
    else:
        _, bs, kv, _ = pool_shape
        aligned = bs % 8 == 0 and (kv % 8 == 0 or kv in (1, 2, 4))
    if d % 128 or not aligned:
        return XLA
    if h * w * d * 4 > _VMEM_BLOCK_BUDGET // 2:
        return XLA
    return PALLAS


def select_latent_attention(q_shape, pool_shape, v_width: int, *,
                            platform=None, is_partitioned=None) -> str:
    """Attention over a paged latent pool (ops/latent_attention.py), decode
    rows and prompt chunks alike. q (B, W, H, row_width); pool (N, bs,
    row_width). Pallas when kernels are on and the trace is not partitioned;
    Mosaic needs the row and its value part in whole lane tiles (the cache
    spec pads the row; a value width that is no multiple of 128 goes to the
    jnp composition) and whole sublane tiles of tokens in a block."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    if backend == INTERPRET:
        return backend
    _, bs, d = pool_shape
    if d % 128 or v_width % 128 or bs % 8:
        return XLA
    return PALLAS


def select_grouped_matmul(x_shape, w_shape, *, platform=None,
                          is_partitioned=None) -> str:
    """Grouped matrix product over the held experts that got rows
    (ops/grouped_matmul.py): x (M, K) sorted by group, w (G, K, N).
    ``"xla"`` is ``jax.lax.ragged_dot``, which XLA:TPU compiles to a grouped
    kernel of its own (no dense product over all groups: seen in the
    compiled text from the sandbox, PR 33); ``"pallas"`` is the megablox
    kernel, which needs lane-aligned K and N and M in whole 128-row tiles
    (``grouped_matmul`` pads the rows up to one: rows after the last group
    cost nothing); fewer than one tile of rows stay with ``"xla"``.
    Chosen by measurement on the v5e at the serving cell's shapes
    (tools/kernel_bench.py --ops experts; PERF.md, PR 33): see
    :data:`GROUPED_MATMUL_ON_TPU`."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend != PALLAS or GROUPED_MATMUL_ON_TPU == XLA:
        return XLA
    m, k = x_shape
    n = w_shape[2]
    if m < 128 or k % 128 or n % 128:
        return XLA
    return PALLAS


# the measured winner of the two grouped products on the v5e: the held
# experts' part of a layer at 128 / 384 rows (512 / 1536 pairs, 32 experts of
# 4096 x 2048) takes 2.17 / 2.56 ms with megablox — 86 / 77 % of the time the
# read of the active experts' matrices alone takes — and 5.11 / 5.27 ms with
# ragged_dot (my chip run, PR 33, tools/expert_bench.py)
GROUPED_MATMUL_ON_TPU = PALLAS


def select_selective_scan(h_shape, tokens: int = 1, *, platform=None,
                          is_partitioned=None) -> str:
    """Selective-scan state update (ops/selective_scan.py): the one-step
    update over all slots (``tokens`` 1) and the chunk scan of one slot.
    h (rows, d_state, d_inner) float32, channels on the lanes. Pallas when
    kernels are on and the trace is not partitioned; Mosaic needs whole
    lane tiles of channels, whole sublane tiles of states, and the chunk's
    lane-padded (tokens, d_state, 1) B and C operands inside the VMEM
    block budget."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    if backend == INTERPRET:
        return backend
    _, s, d = h_shape
    if d % 128 or s % 8:
        return XLA
    if 2 * 2 * tokens * s * 128 * 4 > _VMEM_BLOCK_BUDGET:
        return XLA
    return PALLAS


def select_ssm2_step(h_shape, *, platform=None, is_partitioned=None) -> str:
    """Mamba-2's one-step state update over all slots (ops/ssm2.py). h
    (rows, heads, head_dim, d_state) float32: always the jnp composition,
    BY MEASUREMENT. XLA fuses the update and the read-out into one in-place
    pass over the state: 96 slots of 128 x 64 x 128 (2 x 403 MB a layer) take
    1,249.6 us a layer, 78.7 % of the bandwidth peak. A Pallas kernel written
    beside it (a row's 32 heads a program, the state aliased in place, a
    head's decay and ``dt * x`` handed in as columns and broadcast over the
    lanes, the read-out a lane reduction) took 1,587.5 us (61.9 %): its
    column broadcasts and lane reductions cost the vector unit ~120 cycles a
    head where the copies need 75. It was deleted (my chip run, PR 35,
    tools/expert_bench.py --ops ssm2; PERF.md section 6)."""
    return XLA


def select_ssd_chunk(h_shape, tokens: int, *, platform=None,
                     is_partitioned=None) -> str:
    """Mamba-2's chunk scan of one slot in the chunked (dual) form
    (ops/ssm2.py): always the jnp composition. Its work IS four matrix
    products (2.2 GFLOP a layer at 256 tokens of 128 heads x 64 x 128), which
    XLA puts on the matrix unit; beside the chunk's 52 GFLOP of projections
    a hand-written kernel has nothing to win (PERF.md section 6, PR 35)."""
    return XLA


def lora_block_out(seq: int, in_dim: int, out_dim: int, rank: int,
                   x_dtype, w_dtype) -> int:
    """Output-column tile of the fused LoRA projection: the largest
    128-multiple divisor of ``out_dim`` (<= 512) whose working set fits
    the scoped-VMEM limit with headroom, 0 when none does. Per buffer: the
    x row (S, in), the weight tile (in, tile), the A factor (in, rank) —
    whose minor dim pads to 128 lanes in VMEM, 7.3 MB at in = 14336 — the
    B factor and the output tile; blocks are double-buffered and the f32
    temporaries (x32, y, delta) sit on top."""
    xb, wb = np.dtype(x_dtype).itemsize, np.dtype(w_dtype).itemsize
    lanes = -(-rank // 128) * 128
    fixed = 2 * (seq * in_dim * xb + in_dim * lanes * 4) + seq * in_dim * 4
    for tile in (512, 384, 256, 128):
        if out_dim % tile:
            continue
        per_tile = (2 * (in_dim * tile * wb + max(rank, 8) * tile * 4
                         + seq * tile * xb) + 2 * seq * tile * 4)
        if fixed + per_tile <= 12 * 1024 * 1024:
            return tile
    return 0


def select_lora_matmul(x_shape, w_shape, rank: int, x_dtype, w_dtype, *,
                       platform=None, is_partitioned=None) -> str:
    """Fused base projection + per-row LoRA delta. x (B, S, IN); w
    (IN, OUT). Mosaic needs lane-aligned projection dims and an output
    tile that fits VMEM (:func:`lora_block_out`) — at Llama-3-8B widths
    that keeps q/k/v/o/gate/up (IN 4096) on the kernel and sends down_proj
    (IN 14336: the lane-padded A factor alone is 14.7 MB double-buffered)
    to the jnp composition."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    if backend == INTERPRET:
        return backend
    _, seq, in_dim = x_shape
    out_dim = w_shape[1]
    if in_dim % 128 or out_dim % 128:
        return XLA
    if not lora_block_out(seq, in_dim, out_dim, rank, x_dtype, w_dtype):
        return XLA
    return PALLAS


def select_w8_matmul(m: int, k: int, n: int, *, platform=None,
                     is_partitioned=None) -> str:
    """Weight-only int8 matmul. The streaming kernel only wins when the
    matmul is weight-read bound (single-token decode, M = decode batch
    <= 16); prefill/training shapes reuse each weight block M times and
    take the dequantize-once XLA program."""
    backend = _kernel_backend(platform, is_partitioned)
    if backend is None:
        return XLA
    if k % 128 or n % 128 or m > 16:
        return XLA
    return backend


def select_fused_adamw(shape, *, platform=None, n_devices=None) -> str:
    """Fused AdamW tile update — the shape/platform half of the rule; the
    opt-in switches (``PT_FUSED_ADAMW`` / ``PT_MT_ADAMW``) stay with the
    optimizer. Single device only (the state is ZeRO-sharded otherwise;
    the interpreter is the CPU test seam and exempt)."""
    backend = pallas_backend(platform)
    if backend is None:
        return XLA
    n = jax.device_count() if n_devices is None else n_devices
    if n != 1 and backend != INTERPRET:
        return XLA
    if len(shape) != 2 or shape[0] % 8 or shape[1] % 128:
        return XLA
    return backend
