"""Pallas TPU kernel library — the replacement for the reference's fused CUDA
ops (ref paddle/fluid/operators/fused/: fused_attention_op.cu,
fused_multi_transformer_op.cu, fmha_ref.h) and hand-written PHI GPU kernels.

Kernel selection lives in :mod:`paddle_tpu.ops.select`: one rule per hot op,
decided before the kernel is traced from the platform, the process-wide
kernel mode, GSPMD partitioning and static shapes; a selected kernel that
fails to lower or compile is an error that reaches the caller. The mode
helpers are re-exported here:

* ``use_pallas()`` — a Pallas code path may run: on a TPU, under
  ``PT_FLASH_INTERPRET=1`` (interpret mode on CPU), or when the mode was
  pinned to ``"pallas"``. ``"reference"`` pins the jnp compositions
  regardless of platform.
* ``pallas_interpret()`` — ``pl.pallas_call`` must run interpreted (Pallas
  requested on a platform without the Mosaic compiler).

Both are read at TRACE time, so flipping the mode between compiled program
invocations has no effect — set it before the first trace
(GenerationServer does this in its constructor via ``kernels=``).
"""
from .select import (KERNEL_MODES, kernel_mode, pallas_interpret,
                     set_kernel_mode, use_pallas)

from .flash_attention import flash_attention, flash_attention_bshd
from .fused_norm import fused_rms_norm, fused_layer_norm
from .paged_attention import (gather_block_kv, gather_block_scales,
                              paged_decode_attention,
                              paged_decode_attention_q,
                              paged_prefill_attention,
                              paged_prefill_attention_q,
                              quantize_block_kv, write_chunk_kv,
                              write_chunk_kv_q, write_decode_kv,
                              write_decode_kv_q)

__all__ = ["flash_attention", "flash_attention_bshd", "fused_rms_norm",
           "fused_layer_norm", "gather_block_kv", "gather_block_scales",
           "kernel_mode", "paged_decode_attention",
           "paged_decode_attention_q", "paged_prefill_attention",
           "paged_prefill_attention_q", "pallas_interpret",
           "quantize_block_kv", "set_kernel_mode", "use_pallas",
           "write_chunk_kv", "write_chunk_kv_q", "write_decode_kv",
           "write_decode_kv_q"]
