"""Compile-only check against a TPU v5e target — from a machine with no chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` returns a compile-only v5e target (four devices,
``device_kind`` "TPU v5 lite"). Lowering a jitted function against
``ShapeDtypeStruct``s placed on those devices and calling ``.compile()`` runs
XLA:TPU **and Mosaic** for real, so a kernel the chip's compiler would refuse
is refused here, without spending chip time. It says what the compiler
accepts, not what the chip computes — ``chip_smoke.py`` and ``tests_tpu/``
answer that.

Usage (the platform is pinned in-process; leave ``JAX_PLATFORMS`` unset —
under ``JAX_PLATFORMS=cpu`` paddle_tpu turns on x64 and ``highest`` matmul
precision, and Mosaic rejects kernels that are fine on the chip):

    env -u JAX_PLATFORMS python tools/compile_check.py            # kernels
    env -u JAX_PLATFORMS python tools/compile_check.py --programs # + whole
        # train steps / serving programs, one chip and the 2x2 mesh (minutes)
    env -u JAX_PLATFORMS python tools/compile_check.py --only flash,norm

Prints one line per case and exits non-zero if any case does not compile.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import re
import sys
import time
import traceback

os.environ.pop("JAX_PLATFORMS", None)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

TOPOLOGY = "v5e:2x2"
_BF16 = jnp.bfloat16

# Llama-3-8B widths (the serve leg of chip_smoke.py) and the 509M train proxy
H8, KV8, D8, HID8, FFN8 = 32, 8, 128, 4096, 14336


def target_devices():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def compile_on(fn, specs, sharding):
    """Lower ``fn`` for the target with every operand under ``sharding`` and
    compile. ``specs``: [(shape, dtype), ...]. Returns (compiled, n_mosaic)."""
    args = [_sds(s, d, sharding) for s, d in specs]
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    return compiled, compiled.as_text().count("tpu_custom_call")


# ------------------------------------------------------------- kernel cases
def _flash_case(B, H, KV, S, D):
    from paddle_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32))

    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((B, H, S, D), _BF16), ((B, KV, S, D), _BF16),
             ((B, KV, S, D), _BF16)])


def _norm_case(rows, width, dtype, kind="rms"):
    from paddle_tpu.ops.fused_norm import _ln_pallas, _rms_pallas

    if kind == "rms":
        return (lambda x, w: _rms_pallas(x, w, 1e-6),
                [((rows, width), dtype), ((width,), dtype)])
    return (lambda x, w, b: _ln_pallas(x, w, b, 1e-5),
            [((rows, width), dtype), ((width,), dtype), ((width,), dtype)])


def _paged_case(B, W, quantized, N=512, bs=16, M=32, H=H8, KV=KV8, D=D8,
                head_major=False):
    from paddle_tpu.ops import paged_attention_pallas as pk

    q = ((B, W, H, D), _BF16)
    tables, pos = ((B, M), jnp.int32), ((B,), jnp.int32)
    if not quantized:
        pool = ((N, KV, bs, D) if head_major else (N, bs, KV, D), _BF16)
        return (lambda q_, k, v, t, p: pk.paged_attention(
                    q_, k, v, t, p, head_major=head_major),
                [q, pool, pool, tables, pos])
    pool, sc = ((N, bs, KV, D), jnp.int8), ((N, KV), jnp.float32)
    return (lambda q_, k, ks, v, vs, t, p:
            pk.paged_attention_q(q_, k, ks, v, vs, t, p),
            [q, pool, sc, pool, sc, tables, pos])


def _window_case(B, ring=33, window=512, bs=16, KV=10, H=40, D=128):
    """Decode over a per-slot ring of head-major blocks (the differential
    pair packed 128 wide: Phi-4-mini-flash's window layers; 4 KV heads of
    128 at group 8 and a window of 2,048: Trinity-Mini's)."""
    from paddle_tpu.ops import paged_attention_pallas as pk

    pool = ((1 + B * ring, KV, bs, D), _BF16)
    return (lambda q_, k, v, t, p: pk.paged_attention(
                q_, k, v, t, p, window=window, ring=ring, head_major=True),
            [((B, 1, H, D), _BF16), pool, pool, ((B, ring), jnp.int32),
             ((B,), jnp.int32)])


def _ssm_case(rows, tokens, d=5120, S=16):
    from paddle_tpu.ops import selective_scan as ss

    f = jnp.float32
    if tokens == 1:
        return (ss.ssm_step_pallas,
                [((rows, d), f), ((rows, d), f), ((S, d), f), ((rows, S), f),
                 ((rows, S), f), ((d,), f), ((rows, S, d), f)])
    return (ss.ssm_chunk_scan_pallas,
            [((tokens, d), f), ((tokens, d), f), ((S, d), f), ((tokens, S), f),
             ((tokens, S), f), ((d,), f), ((S, d), f)])


def _ssm2_case(rows, tokens, H=128, P=64, N=128):
    """Mamba-2's state update at the Granite 4.0-H cell's shapes: the
    one-step update over 96 slots and the chunk scan of one slot in the
    chunked form (jnp compositions both: no Mosaic call)."""
    from paddle_tpu.ops import ssm2

    f = jnp.float32
    if tokens == 1:
        return (ssm2.ssm2_step,
                [((rows, H, P), f), ((rows, H), f), ((H,), f), ((rows, N), f),
                 ((rows, N), f), ((H,), f), ((rows, H, P, N), f)])
    return (ssm2.ssd_chunk,
            [((tokens, H, P), f), ((tokens, H), f), ((H,), f),
             ((tokens, N), f), ((tokens, N), f), ((H,), f), ((H, P, N), f)])


def _latent_case(B, W, N=8501, bs=64, M=196, H=32, D=384, Dv=256):
    """Attention over a paged latent pool at the Mistral-Small-4 cell's
    shapes: 128 slots, 12,288-token tables (+ a chunk of slack) in blocks of
    64, rows of 256 + 64 values in three lane tiles, 32 heads."""
    from paddle_tpu.ops import latent_attention_pallas as lk

    return (lambda q, pool, t, p: lk.latent_attention(
                q, pool, t, p, v_width=Dv, scale=0.1949, qscale=(0.1, 8192)),
            [((B, W, H, D), _BF16), ((N, bs, D), _BF16), ((B, M), jnp.int32),
             ((B,), jnp.int32)])


def _gmm_case(rows, impl, E=32, K=4096, N=2048):
    """The grouped product over the held experts, both implementations."""
    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    return (lambda x, w, g: grouped_matmul(x, w, g, impl=impl),
            [((rows, K), _BF16), ((E, K, N), _BF16), ((E,), jnp.int32)])


_CELL = dict(N=4096, bs=16, M=256)
# Phi-4-mini-flash's cell: 128 slots, 8192-token tables, 10 packed kv heads
# (no sublane multiple: head-major blocks)
_PHI = dict(N=32769, bs=16, M=520, H=40, KV=10, D=128, head_major=True)
_TRINITY = dict(N=62501, bs=16, M=1152, H=32, KV=4, D=128, head_major=True)
_CELL_INT8 = dict(N=4096, bs=32, M=128)     # same 4096-token tables


def _lora_case(B, S, IN, OUT, R=16):
    from paddle_tpu.ops.paged_attention_pallas import fused_lora_matmul

    return (fused_lora_matmul,
            [((B, S, IN), _BF16), ((IN, OUT), _BF16),
             ((B, IN, R), jnp.float32), ((B, R, OUT), jnp.float32),
             ((B,), jnp.float32)])


def _w8_case(M, K, N):
    from paddle_tpu.ops.int8 import _w8_matmul_pallas

    return (lambda x, w, s: _w8_matmul_pallas(x, w, s, _BF16),
            [((M, K), _BF16), ((K, N), jnp.int8), ((N,), jnp.float32)])


def kernel_cases():
    """(name, builder) for every kernel the ``auto`` rule can select at the
    chip_smoke / tests_tpu shapes."""
    return [
        ("flash.train_509m_S2048", lambda: _flash_case(8, 16, 8, 2048, 128)),
        ("flash.gqa32_8_S2048", lambda: _flash_case(1, H8, KV8, 2048, D8)),
        ("flash.stream_S16384", lambda: _flash_case(1, 4, 2, 16384, 128)),
        ("flash.head_dim_64", lambda: _flash_case(4, 4, 2, 512, 64)),
        ("norm.rms_bf16_16384x2048",
         lambda: _norm_case(16384, 2048, _BF16)),
        ("norm.rms_bf16_8192x4096", lambda: _norm_case(8192, HID8, _BF16)),
        ("norm.rms_f32_8192x4096",
         lambda: _norm_case(8192, HID8, jnp.float32)),
        ("norm.rms_bf16_decode_8x4096", lambda: _norm_case(8, HID8, _BF16)),
        ("norm.rms_bf16_odd_40x4096", lambda: _norm_case(40, HID8, _BF16)),
        ("norm.ln_f32_8192x4096",
         lambda: _norm_case(8192, HID8, jnp.float32, "ln")),
        ("paged.fp_decode_W1", lambda: _paged_case(8, 1, False)),
        ("paged.fp_verify_W4", lambda: _paged_case(8, 4, False)),
        ("paged.fp_prefill_C128", lambda: _paged_case(1, 128, False)),
        ("paged.int8_decode_W1", lambda: _paged_case(8, 1, True, bs=32)),
        ("paged.int8_verify_W4", lambda: _paged_case(8, 4, True, bs=32)),
        ("paged.int8_prefill_C128", lambda: _paged_case(1, 128, True, bs=32)),
        # the serving cells' own shapes (benchmarks/configs): 64 slots,
        # 4096-token tables, the 4 GiB pool
        ("paged.cell_fp_decode_B64_M256",
         lambda: _paged_case(64, 1, False, **_CELL)),
        ("paged.cell_fp_verify_W4_B64",
         lambda: _paged_case(64, 4, False, **_CELL)),
        ("paged.cell_fp_prefill_C128_M256",
         lambda: _paged_case(1, 128, False, **_CELL)),
        ("paged.cell_int8_decode_B64_M256",
         lambda: _paged_case(64, 1, True, **_CELL_INT8)),
        ("paged.cell_int8_prefill_C128",
         lambda: _paged_case(1, 128, True, **_CELL_INT8)),
        ("paged.phi_pair_decode_B128_M520",
         lambda: _paged_case(128, 1, False, **_PHI)),
        ("paged.phi_pair_prefill_C128_M520",
         lambda: _paged_case(1, 128, False, **_PHI)),
        ("paged.phi_pair_last_token_B1",
         lambda: _paged_case(1, 1, False, **_PHI)),
        ("paged.phi_window_decode_B128_ring33", lambda: _window_case(128)),
        # the Trinity-Mini cell: head-major blocks of 4 KV heads of 128 at
        # group 8 — rings of 129 blocks over a window of 2,048, the full
        # layers' tables of 18,432 tokens, a chunk's 128 query rows a call
        ("paged.trinity_window_decode_B128_ring129", lambda: _window_case(
            128, ring=129, window=2048, KV=4, H=32)),
        ("paged.trinity_full_decode_B128_M1152", lambda: _paged_case(
            128, 1, False, **_TRINITY)),
        ("paged.trinity_full_prefill_C128_M1152", lambda: _paged_case(
            1, 128, False, **_TRINITY)),
        ("latent.decode_B128_M196", lambda: _latent_case(128, 1)),
        ("latent.chunk_C256_M196", lambda: _latent_case(1, 256)),
        ("latent.decode_B128_bs16_M784",
         lambda: _latent_case(128, 1, N=40001, bs=16, M=784)),
        ("experts.ragged_dot_512_pairs", lambda: _gmm_case(512, "xla")),
        ("experts.ragged_dot_1536_pairs", lambda: _gmm_case(1536, "xla")),
        ("experts.megablox_512_pairs", lambda: _gmm_case(512, "pallas")),
        ("experts.megablox_1536_pairs", lambda: _gmm_case(1536, "pallas")),
        # the Granite 4.0-H cell: 36 held experts of 4096 x 768; a tick's
        # 96 x 10 pairs (no whole row tile: padded up) and a chunk's 2,560
        ("experts.megablox_960_pairs_w768",
         lambda: _gmm_case(960, "pallas", E=36, N=768)),
        ("experts.megablox_2560_pairs_w768_down",
         lambda: _gmm_case(2560, "pallas", E=36, K=768, N=4096)),
        ("experts.ragged_dot_960_pairs_w768",
         lambda: _gmm_case(960, "xla", E=36, N=768)),
        # the Trinity-Mini cell: 64 held experts of 2048 x 1024; a tick's
        # 128 x 8 pairs (about half of them held) and a chunk's 2,048
        ("experts.megablox_512_pairs_e64_w1024",
         lambda: _gmm_case(512, "pallas", E=64, K=2048, N=1024)),
        ("experts.megablox_2048_pairs_e64_w1024_down",
         lambda: _gmm_case(2048, "pallas", E=64, K=1024, N=2048)),
        ("experts.ragged_dot_512_pairs_e64_w1024",
         lambda: _gmm_case(512, "xla", E=64, K=2048, N=1024)),
        ("ssm2.step_B96_128x64x128", lambda: _ssm2_case(96, 1)),
        ("ssm2.chunk_C256_128x64x128", lambda: _ssm2_case(1, 256)),
        ("ssm.step_B128_5120x16", lambda: _ssm_case(128, 1)),
        ("ssm.chunk_scan_C128_5120x16", lambda: _ssm_case(1, 128)),
        ("lora.decode_4096x4096", lambda: _lora_case(8, 1, HID8, HID8)),
        ("lora.decode_4096x14336", lambda: _lora_case(8, 1, HID8, FFN8)),
        ("w8.decode_4096x14336", lambda: _w8_case(8, HID8, FFN8)),
        ("w8.decode_14336x4096", lambda: _w8_case(8, FFN8, HID8)),
    ]


def weight_read_bytes(hlo_text):
    """{weight's path: bytes of it that the ENTRY computation's
    instructions read} for the parameters named as weights, from a compiled
    program's text: an async ``slice-start`` reads its slice, every other
    user the whole array. What says whether a program that joins two steps
    streams a weight once or once per step."""
    size = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4}
    lines = hlo_text[hlo_text.index("\nENTRY"):].split("\n")[1:]
    out = {}
    for line in lines:
        m = re.match(r"\s*(%\S+) = (\w+)\[([\d,]*)\]\S* parameter\(.*"
                     r"op_name=\"([^\"]*weight[^\"]*)\"", line)
        if m is None:
            continue
        name, whole = m.group(1), size[m.group(2)] * int(np.prod(
            [int(d) for d in m.group(3).split(",") if d]))
        read = 0
        for user in lines:
            head, _, body = user.partition(" = ")
            if head.strip() == name or not re.search(
                    re.escape(name) + r"[,)]", body):
                continue
            cut = re.search(r"slice-start\(.*slice=\{([^}]*)\}", body)
            if cut is None:
                read += whole
            else:
                ext = [int(b) - int(a) for a, b in
                       re.findall(r"\[(\d+):(\d+)\]", cut.group(1))]
                read += size[m.group(2)] * int(np.prod(ext))
        out[m.group(4)] = read
    return out


def reads_weights_like(other, small=0):
    """A case's check: no weight is read more in this program than in the
    case ``other`` (the decode program, which reads each once and the
    cross-program prefetch's twice). Weights of at most ``small`` bytes are
    let off: what each kind of row applies for itself (a convolution's
    taps, a scan's per-head constants) is read once per kind."""
    def check(text, texts, temps):
        if other not in texts:
            return " weight_reads=unchecked"
        mine, ref = weight_read_bytes(text), weight_read_bytes(texts[other])
        more = {k: (v, ref.get(k)) for k, v in mine.items()
                if v > max(ref.get(k, 0), small)}
        if more or not mine:
            raise RuntimeError(f"weights read more than in {other}: {more}")
        return (f" weight_reads={sum(mine.values()) / sum(ref.values()):.2f}"
                f"x_decode")
    return check


def _whole_copies(text, operand, dtype, what):
    """Fail if the compiled program's entry copies, whole, an array it was
    handed under the operand name ``operand`` (HLO dtype pattern ``dtype``)."""
    entry = text[text.index("\nENTRY"):]
    arrays = set(re.findall(
        r"= (" + dtype + r"\[[\d,]+\])\S* parameter\(\d+\).*op_name=\""
        + operand, entry))
    n = sum(len(re.findall(r"= " + re.escape(a) + r"\S* copy\(", entry))
            for a in arrays)
    if n or not arrays:
        raise RuntimeError(f"{n} whole-{what} copies ({what}s: "
                           f"{sorted(arrays)})")
    return f" {what}_copies=0"


def copies_no_pool(text, texts, temps):
    """A case's check: the compiled program copies no KV pool whole. A pool
    is updated in place (donated, aliased to its output); where a write sits
    between two readers the compiler keeps the old pool for the first and
    copies all of it — 134 MB a pool a call at the Mistral cells' size,
    which the joint decode + chunk step did in its first layer until both
    writes were put before both attention calls (PR 32)."""
    return _whole_copies(text, "flat_pools", r"\w+", "pool")


def copies_no_state(text, texts, temps):
    """A case's check: the compiled program copies no float32 slot-state
    array whole (a Mamba-2 layer's state is 403 MB over 96 slots: it is
    donated and updated in place, a row of it or all rows)."""
    return _whole_copies(text, "slot_pools", "f32", "state")


def copies_no_ring(text, texts, temps):
    """A case's check: the compiled program copies no window layer's ring
    pool whole (bfloat16 slot pools: 541 MB a pool over 128 slots of
    Trinity-Mini's rings; donated and written a token or a chunk at a
    time)."""
    return _whole_copies(text, "slot_pools", "bf16", "ring")


def makes_no_second(shape, temps_gb):
    """A case's check: the compiled program makes no second array of the
    HLO shape ``shape`` (a slot-state array: ``f32[96,128,64,128]`` is 403 MB)
    anywhere — not in its entry, not in a conditional's branch, not inside a
    fusion: no ``copy`` to that shape, no fusion that returns the shape
    without writing into its operand (a ``dynamic-update-slice`` at its
    root), and temps under ``temps_gb``, which one such array would pass."""
    def check(text, texts, temps):
        made = re.findall(r"= " + re.escape(shape) + r"\S* copy\(", text)
        roots = dict(re.findall(
            r"\n(%[\w.\-]+) \([^\n]*\{\n(?:[^\n]*\n)*?\s*ROOT [^\n]* = "
            r"[^\n]*? ([\w\-]+)\(", text))
        for called in re.findall(r"= " + re.escape(shape) + r"\S* fusion\("
                                 r"[^\n]*calls=(%[\w.\-]+)", text):
            if roots.get(called) != "dynamic-update-slice":
                made.append(f"fusion {called} -> {roots.get(called)}")
        if made or temps > temps_gb * 1e9:
            raise RuntimeError(
                f"{len(made)} new {shape} arrays ({made[:3]}), temps "
                f"{temps / 1e9:.2f} GB against {temps_gb}")
        return f" new_{shape.split('[')[0]}_states=0"
    return check


def copies_no_stack(*shapes):
    """A case's check: nothing in the compiled program produces a second
    array of an expert stack's shape (``shapes``: HLO shape texts) — no
    copy, transpose, convert or fusion of a whole [held, in, out] stack: the
    grouped products read the weights where they lie. (A fusion that only
    bitcasts its operand makes no array.)"""
    def check(text, texts, temps):
        hits = []
        for shape in shapes:
            hits += re.findall(
                r"= " + re.escape(shape) + r"\S* (?!parameter|get-tuple-"
                r"element|bitcast)[\w\-]+\((?!.*calls=%bitcast_fusion)", text)
        if hits:
            raise RuntimeError(f"{len(hits)} whole expert stacks made anew: "
                               f"{sorted(set(hits))[:4]}")
        return " stack_copies=0"
    return check


# ------------------------------------------------------------ program cases
# Whole programs, through the same entry points chip_smoke.py drives, at
# Llama-3-8B WIDTHS but two layers deep (layers unroll: depth only multiplies
# compile time). Each returns (lowered-able jitted fn, abstract args).
def _abstract(tree, sharding_of):
    """Every array leaf -> ShapeDtypeStruct placed by ``sharding_of(leaf)``."""
    return jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, sharding_of(a))
        if hasattr(a, "shape") else a, tree)


def _train_program(devs, mesh_shape, axes, cfg, B, S, **engine_kw):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    n = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(devs[:n]).reshape(mesh_shape), axes)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = ParallelEngine(model, optimizer=opt, loss_fn=None, mesh=mesh,
                         abstract=True, **engine_kw)
    step = eng.build_train_step()
    bspec = eng._batch_sharding(np.zeros((B, S)), eng.batch_spec)
    batch = (_sds((B, S), jnp.int32, bspec), _sds((B, S), jnp.int64, bspec))
    rep = NamedSharding(mesh, P())
    return step, (eng.params, eng.opt_state, _sds((), jnp.int32, rep),
                  1e-3, batch)


def _serve_programs(devs, cfg, tp, model_cls=None, **served):
    """(decode, chunk, reference-forward) of a paged server."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import mesh_context
    from paddle_tpu.parallel import serving_mesh as sm

    paddle.seed(0)
    model = (model_cls or LlamaForCausalLM)(cfg)
    served = {**dict(max_batch=8, max_len=512, block_size=16,
                     prefill_chunk=128, num_blocks=256), **served}
    srv = GenerationServer(model, cache="paged",
                           mesh=None if tp == 1 else f"tp={tp}", **served)
    slot_pools = []
    if tp == 1:
        one = SingleDeviceSharding(devs[0])
        mesh = None
        params = _abstract(srv.params, lambda a: one)
        pools = _abstract(srv._pools, lambda a: one)
        slot_pools = _abstract(srv._slot_pools, lambda a: one)

        def rep(shape, dtype):
            return _sds(shape, dtype, one)
    else:
        mesh = Mesh(np.array(devs[:tp]), (sm.SERVING_TP_AXIS,))
        specs = sm.serving_param_specs(model, mesh)
        params = {k: _sds(v.shape, v.dtype,
                          NamedSharding(mesh, specs.get(k, P())))
                  for k, v in srv.params.items()}
        pools = [_sds(v.shape, v.dtype,
                      NamedSharding(mesh, sm.pool_spec(v.ndim)))
                 for v in srv._pools]

        def rep(shape, dtype):
            return _sds(shape, dtype, NamedSharding(mesh, P()))

    B, M = srv.max_batch, srv._bt.shape[1]
    f32, i32 = jnp.float32, jnp.int32
    decode_args = (params, rep((B,), i32), pools, rep((B, M), i32),
                   rep((B,), i32), rep((B,), f32), rep((B,), i32),
                   rep((B,), f32), rep((B,), i32), rep((2,), jnp.uint32),
                   None, (), True, None, slot_pools,
                   rep((srv.tick_window, B), i32))   # the pending trip's stack
    prefill_args = (params, rep((1, srv.prefill_chunk), i32), pools,
                    rep((M,), i32), rep((), i32), rep((), i32), None, (),
                    slot_pools, rep((3,), i32))

    # the chunk program the server dispatches: where a chunk can ride in
    # the decode trip's call (executor.py, ``chunk_alone_why``) the joint
    # program over B + C rows, else the chunk program
    if srv._decode_chunk is not None:
        chunk_prog = (srv._decode_chunk, decode_args[:10] + (
            rep((1, B), i32), rep((1, srv.prefill_chunk), i32),
            rep((M,), i32), rep((), i32), rep((), i32), True, slot_pools,
            rep((3,), i32) if slot_pools else None,
            rep((), i32)))                  # the slot a first token goes to
    else:
        chunk_prog = (srv._chunk_prefill, prefill_args)

    def fwd(p, ids):
        with (mesh_context(mesh) if mesh is not None
              else contextlib.nullcontext()):
            return functional_call(model, p, Tensor(ids)).value

    return [(srv._decode_paged, decode_args), chunk_prog,
            (jax.jit(fwd), (params, rep((1, srv.max_len), i32)))]


def program_cases(devs):
    from paddle_tpu.models import LlamaConfig, llama3_8b_config

    proxy = LlamaConfig(vocab_size=32000, hidden_size=2048,
                        intermediate_size=5632, num_hidden_layers=8,
                        num_attention_heads=16, num_key_value_heads=8,
                        max_position_embeddings=2048, dtype="bfloat16")

    def wide(**kw):
        return llama3_8b_config(num_hidden_layers=2, **kw)

    built = {}          # one model + server per tp, shared by its 3 cases

    def serve(tp, i):
        def build():
            if tp not in built:
                # one chip: the Mistral cells' served shape (64 slots of
                # 4096, chunks of 128), so that the temps are the cells'
                shape = {} if tp > 1 else dict(
                    max_batch=64, max_len=4096, num_blocks=4097)
                built[tp] = _serve_programs(
                    devs, wide(max_position_embeddings=shape.get(
                        "max_len", 512)), tp, **shape)
            return built[tp][i]
        return build

    def mistral_cell():
        """The joint decode + chunk program as the Mistral cells run it:
        16 layers at the published widths, 64 slots of 4096, the 4 GiB
        pool (what the 2-layer case cannot show: PR 32's whole-pool copies
        appeared with 16 layers only)."""
        return _serve_programs(
            devs, llama3_8b_config(num_hidden_layers=16, vocab_size=32768,
                                   max_position_embeddings=4096), 1,
            max_batch=64, max_len=4096, num_blocks=4096)[1]

    def phi(i):
        """Phi-4-mini-flash-reasoning whole, as its benchmark cell serves
        it (benchmarks/configs/phi-4-mini-flash-reasoning.json): argument
        and temp bytes of the two programs against the chip's 16 GB."""
        def build():
            if "phi" not in built:
                from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                                         Phi4FlashForCausalLM)

                built["phi"] = _serve_programs(
                    devs, Phi4FlashConfig(max_position_embeddings=8192), 1,
                    model_cls=Phi4FlashForCausalLM, max_batch=128,
                    max_len=8192, num_blocks=32769)
            return built["phi"][i]
        return build

    def mistral4(i):
        """Mistral-Small-4's share as its benchmark cell serves it
        (benchmarks/configs/mistral-small-4-119b-l6-ep4.json): 6 layers at
        the published widths, experts 0-31 of 128, a quarter of the
        vocabulary, 128 slots of 12,288, blocks of 64, chunks of 256, the
        640k-token latent pool. ~16 GB of host RAM."""
        def build():
            if "mistral4" not in built:
                from paddle_tpu.models.mistral4 import (Mistral4Config,
                                                        Mistral4ForCausalLM)

                built["mistral4"] = _serve_programs(
                    devs, Mistral4Config(
                        num_hidden_layers=6, vocab_size=32768,
                        experts_held=(0, 32), max_position_embeddings=12288),
                    1, model_cls=Mistral4ForCausalLM, max_batch=128,
                    max_len=12288, block_size=64, prefill_chunk=256,
                    num_blocks=8501)
            return built["mistral4"][i]
        return build

    def trinity(i):
        """Trinity-Mini's share as its benchmark cell serves it
        (benchmarks/configs/trinity-mini-l8-ep2.json) but one period deep —
        window layers 0-2 (0-1 dense), a full layer at 3 with experts 2-3 —
        at the published widths, experts 0-63 of 128, half of the
        vocabulary, 128 slots of 18,432 with 3 x 4.2 MB of rings each,
        blocks of 16, chunks of 256, a quarter of the cell's pool. ~10 GB
        of host RAM."""
        def build():
            if "trinity" not in built:
                from paddle_tpu.models.afmoe import (AfmoeConfig,
                                                     AfmoeForCausalLM)

                built["trinity"] = _serve_programs(
                    devs, AfmoeConfig(
                        num_hidden_layers=4, vocab_size=100096,
                        experts_held=(0, 64), max_position_embeddings=18432),
                    1, model_cls=AfmoeForCausalLM, max_batch=128,
                    max_len=18432, block_size=16, prefill_chunk=256,
                    num_blocks=16385)
            return built["trinity"][i]
        return build

    def granite4h(i):
        """Granite 4.0-H Small's share as its benchmark cell serves it
        (benchmarks/configs/granite-4.0-h-small-l10-ep2.json): 10 layers at
        the published widths (nine Mamba-2, attention at 5), experts 0-35 of
        72, half of the vocabulary, 96 slots of 3,072 with 38 MB of state
        each, blocks of 16, chunks of 256. ~16 GB of host RAM."""
        def build():
            if "granite4h" not in built:
                from paddle_tpu.models.granitemoehybrid import (
                    GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)

                built["granite4h"] = _serve_programs(
                    devs, GraniteMoeHybridConfig(
                        num_hidden_layers=10, vocab_size=50176,
                        experts_held=(0, 36), max_position_embeddings=3072),
                    1, model_cls=GraniteMoeHybridForCausalLM, max_batch=96,
                    max_len=3072, block_size=16, prefill_chunk=256,
                    num_blocks=7693)
            return built["granite4h"][i]
        return build

    stacks = copies_no_stack("bf16[32,4096,2048]", "bf16[32,2048,4096]")
    stacks768 = copies_no_stack("bf16[36,4096,768]", "bf16[36,768,4096]")
    # (the decode program makes no second tied embedding either; the chunk
    # program's one-row head is a fused multiply and reduce over the table,
    # which reads as one inside its fusion: its temp bytes say it is none)
    embedding = copies_no_stack("bf16[50176,4096]", "bf16[4096,50176]")
    stacks1024 = copies_no_stack("bf16[64,2048,1024]", "bf16[64,1024,2048]")
    return [
        # the joint program: a window layer's chunk reads its ring's past
        # before any write, then both writes, then both attentions
        ("serve.trinity_l4_decode_B128", trinity(0), copies_no_pool,
         copies_no_ring, stacks1024),
        ("serve.trinity_l4_decode_chunk", trinity(1), copies_no_pool,
         copies_no_ring, stacks1024,
         reads_weights_like("serve.trinity_l4_decode_B128")),
        ("serve.granite4h_l10_decode_B96", granite4h(0), copies_no_pool,
         copies_no_state, stacks768, embedding),
        # the joint decode + chunk program, which is every chunk's (PR 36):
        # the state is updated in place behind a conditional (no update
        # where no row decodes) and written at the chunk's slot, the weights
        # are read as often as by the decode program (what each kind of row
        # applies itself — conv taps, dt_bias, A_log, D: 70 KB a layer — by
        # both)
        ("serve.granite4h_l10_decode_chunk", granite4h(1), copies_no_pool,
         copies_no_state, makes_no_second("f32[96,128,64,128]", 0.35),
         stacks768, embedding,
         reads_weights_like("serve.granite4h_l10_decode_B96", small=1 << 18)),
        ("serve.mistral4_l6_decode_B128", mistral4(0), copies_no_pool,
         stacks),
        ("serve.mistral4_l6_decode_chunk", mistral4(1), copies_no_pool,
         stacks, reads_weights_like("serve.mistral4_l6_decode_B128")),
        ("train.1chip_509m_B4_S2048", lambda: _train_program(
            devs, (1,), ("data",), proxy, 4, 2048)),
        ("train.2x2_fsdp_8b_width_L2", lambda: _train_program(
            devs, (2, 2), ("sharding", "tensor"),
            wide(max_position_embeddings=2048), 4, 2048, fsdp=True,
            batch_spec=P(("data", "sharding")))),
        ("serve.1chip_decode", serve(1, 0)),
        ("serve.1chip_decode_chunk", serve(1, 1),
         reads_weights_like("serve.1chip_decode")),
        ("serve.1chip_reference_forward", serve(1, 2)),
        ("serve.tp4_decode", serve(4, 0)),
        ("serve.tp4_decode_chunk", serve(4, 1),
         reads_weights_like("serve.tp4_decode")),
        ("serve.tp4_reference_forward", serve(4, 2)),
        ("serve.mistral_l16_decode_chunk", mistral_cell,
         copies_no_pool),
        ("serve.phi4flash_decode_B128", phi(0)),
        ("serve.phi4flash_prefill_chunk", phi(1)),
    ]


def run_cases(cases, sharding, only=()):
    failed, texts = [], {}
    for name, build, *check in cases:
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.time()
        try:
            fn, specs = build()
            if sharding is None:       # a program case: args are abstract
                compiled = fn.lower(*specs).compile()
                text = texts[name] = compiled.as_text()
                n = text.count("tpu_custom_call")
                mem = compiled.memory_analysis()
                extra = (f" args={mem.argument_size_in_bytes / 1e9:.2f}GB "
                         f"temps={mem.temp_size_in_bytes / 1e9:.2f}GB")
                for c in check:        # what else the compiled text must show
                    extra += c(text, texts, mem.temp_size_in_bytes)
            else:
                _, n = compile_on(fn, specs, sharding)
                extra = ""
            print(f"ok       {name:34s} mosaic_calls={n:<3d} "
                  f"{time.time() - t0:5.1f}s{extra}", flush=True)
        except Exception as e:  # noqa: BLE001 — report, then fail the run
            msg = " ".join(str(e).split())[:300]
            print(f"REFUSED  {name:34s} {type(e).__name__}: {msg}",
                  flush=True)
            if os.environ.get("COMPILE_CHECK_TRACE"):
                traceback.print_exc()
            failed.append(name)
    return failed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated case-name prefixes")
    ap.add_argument("--programs", action="store_true",
                    help="also compile whole train/serve programs (minutes)")
    args = ap.parse_args()
    only = tuple(o for o in args.only.split(",") if o)

    from paddle_tpu.ops.select import target_platform

    devs = target_devices()
    print(f"target: {TOPOLOGY} device_kind={devs[0].device_kind!r} "
          f"devices={len(devs)}")
    with target_platform("tpu"):
        failed = run_cases(kernel_cases(), SingleDeviceSharding(devs[0]),
                           only)
        if args.programs:
            failed += run_cases(program_cases(devs), None, only)
    if failed:
        print(f"{len(failed)} case(s) refused: {', '.join(failed)}")
        return 1
    print("all cases compile")
    return 0


if __name__ == "__main__":
    sys.exit(main())
