"""Per-layer kernel-geometry tier (autotune/kernel_geometry.py + the
geometry-threaded ops): every swept schedule candidate must be
BIT-exact vs the default kernel — paged attention fp+int8 under scratch
poison and mid-block positions (an explicit blocks-per-group regroups
the online softmax: held to 1e-5 and to greedy-token identity instead),
fused LoRA rank padding / issue order,
flash block_q, norm / CE row tiles — the winner cache round-trips and
fails loudly on tamper, degrades to defaults on unknown chips,
TunedProfile v3 carries it (v2 refuses: retune rather than guess), the
sweep is byte-deterministic under a counting clock with parity
hard-rejects, and a profile-geometry server holds zero steady-state
recompiles with snapshots refusing cross-geometry restores."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops
from paddle_tpu.autotune.kernel_geometry import (
    CEGeometry, FlashAttentionGeometry, GeometryCache, LoRAGeometry,
    NormGeometry, PagedAttentionGeometry, _largest_divisor,
    default_geometry, geometry_candidates, install_geometry_cache,
    local_device_kind, resolve_geometry, resolve_server_geometries)
from paddle_tpu.autotune.search import sweep_kernel_geometry
from paddle_tpu.ops import paged_attention_pallas as pap
from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
from paddle_tpu.ops.fused_norm import _ln_pallas, _rms_pallas
from paddle_tpu.ops.paged_attention import quantize_block_kv


@pytest.fixture(autouse=True)
def _reset_geometry_and_mode():
    """The winner cache is process-global trace-time state — a leaked
    install would silently re-schedule every later kernel test."""
    yield
    install_geometry_cache(None)
    ops.set_kernel_mode("auto")


def _paged_case(seed=0, B=3, W=4, H=8, KV=2, D=64, N=16, bs=8,
                pos=(10, 17, 33), poison=True):
    """test_paged_pallas's block-table case (poisoned scratch block 0,
    positions mid-block / at a boundary), with the max position pushed
    to 33 so the table width M=6 takes more than one group — the
    kv_block_depth axis must actually split the block walk (depth 2 -> 3
    groups at the longest row, depth 4 -> 2, the second one partial)."""
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


def _bitexact(ref, out):
    ref, out = np.asarray(ref), np.asarray(out)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_array_equal(ref, out)


def _same_schedule_math(ref, out, geom):
    """q-row tiling and cast placement never touch the math: bitwise.
    An explicit blocks-per-group moves the online-softmax update
    boundaries: same values to 1e-5 (f32)."""
    if geom.kv_block_depth == 0:
        _bitexact(ref, out)
    else:
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


# ======================================================================
# bit-exactness: paged attention
# ======================================================================

PA_FP_GEOMS = [
    PagedAttentionGeometry(kv_block_depth=2),
    PagedAttentionGeometry(kv_block_depth=4),
    PagedAttentionGeometry(q_rows=8),
    PagedAttentionGeometry(q_rows=16),
    PagedAttentionGeometry(kv_block_depth=2, q_rows=8),
]

PA_INT8_GEOMS = PA_FP_GEOMS + [
    PagedAttentionGeometry(dequant="early"),
    PagedAttentionGeometry(kv_block_depth=2, dequant="early"),
    PagedAttentionGeometry(q_rows=8, dequant="early"),
]


class TestPagedAttentionBitExact:
    # W=4 (the spec-verify window) doubles the compile bill per geometry;
    # tier-1 keeps the W=1 sweep and stage 7k runs the full file.
    @pytest.mark.parametrize(
        "W", [1, pytest.param(4, marks=pytest.mark.slow)])
    def test_fp_candidates_match_default_bitwise(self, W):
        q, kp, vp, tables, pos = _paged_case(W=W)
        ops.set_kernel_mode("pallas")
        ref = pap.paged_attention(q, kp, vp, tables, pos,
                                  geometry=PagedAttentionGeometry())
        assert np.isfinite(np.asarray(ref)).all()   # poison held off
        for g in PA_FP_GEOMS:
            out = pap.paged_attention(q, kp, vp, tables, pos, geometry=g)
            _same_schedule_math(ref, out, g)

    @pytest.mark.parametrize(
        "W", [1, pytest.param(4, marks=pytest.mark.slow)])
    def test_int8_candidates_match_default_bitwise(self, W):
        q, kp, vp, tables, pos = _paged_case(W=W, poison=False)
        kq, ks = quantize_block_kv(kp)
        vq, vs = quantize_block_kv(vp)
        ops.set_kernel_mode("pallas")
        ref = pap.paged_attention_q(q, kq, ks, vq, vs, tables, pos,
                                    geometry=PagedAttentionGeometry())
        for g in PA_INT8_GEOMS:
            out = pap.paged_attention_q(q, kq, ks, vq, vs, tables, pos,
                                        geometry=g)
            _same_schedule_math(ref, out, g)

    def test_installed_cache_resolves_at_trace_time(self):
        """geometry=None consults the process-wide cache — the seam the
        server uses — and the non-default winner is the kernel that ran:
        bitwise the explicit geometry's output."""
        q, kp, vp, tables, pos = _paged_case()
        ops.set_kernel_mode("pallas")
        ref = pap.paged_attention(q, kp, vp, tables, pos)
        win = PagedAttentionGeometry(kv_block_depth=2, q_rows=8)
        cache = GeometryCache()
        cache.put("paged_attention", "float32", 64, local_device_kind(),
                  win)
        install_geometry_cache(cache, source="swept")
        geom, src = resolve_geometry("paged_attention", "float32", 64)
        assert src == "swept" and geom.kv_block_depth == 2
        out = pap.paged_attention(q, kp, vp, tables, pos)
        _same_schedule_math(ref, out, win)
        _bitexact(pap.paged_attention(q, kp, vp, tables, pos, geometry=win),
                  out)


# ======================================================================
# bit-exactness: fused LoRA / norm / CE / flash
# ======================================================================

class TestFusedLoRABitExact:
    def _case(self):
        rng = np.random.default_rng(1)
        B, S, IN, OUT, R = 3, 1, 48, 96, 4
        x = jnp.asarray(rng.standard_normal((B, S, IN)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((IN, OUT)).astype(np.float32))
        a = jnp.asarray(rng.standard_normal((B, IN, R)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((B, R, OUT)).astype(np.float32))
        s = jnp.asarray(np.array((0.5, 0.0, 2.0), np.float32))  # null slot
        return x, w, a, b, s

    def test_candidates_match_default_bitwise(self):
        x, w, a, b, s = self._case()
        ops.set_kernel_mode("pallas")
        ref = pap.fused_lora_matmul(x, w, a, b, s, geometry=LoRAGeometry())
        for g in (LoRAGeometry(rank_pad=8), LoRAGeometry(rank_pad=16),
                  LoRAGeometry(accum="delta_first"),
                  LoRAGeometry(rank_pad=8, accum="delta_first")):
            out = pap.fused_lora_matmul(x, w, a, b, s, geometry=g)
            _bitexact(ref, out)
            assert g.padded_rank(4) in (4, 8, 16)


class TestNormCEBitExact:
    def test_rms_and_ln_row_tiles(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((32, 128)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((128,)).astype(np.float32))
        bias = jnp.asarray(rng.standard_normal((128,)).astype(np.float32))
        ref_rms = _rms_pallas(x, w, 1e-6, geometry=NormGeometry(),
                              interpret=True)
        ref_ln = _ln_pallas(x, w, bias, 1e-6, geometry=NormGeometry(),
                            interpret=True)
        for rows in (8, 16, 64):   # 64 clamps onto the 32-row shape
            g = NormGeometry(rows=rows)
            _bitexact(ref_rms, _rms_pallas(x, w, 1e-6, geometry=g,
                                           interpret=True))
            _bitexact(ref_ln, _ln_pallas(x, w, bias, 1e-6, geometry=g,
                                         interpret=True))

    def test_ce_row_subtiles_value_and_grad(self):
        rng = np.random.default_rng(3)
        T, H, V = 64, 32, 128
        h = jnp.asarray(rng.standard_normal((T, H)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((H, V)).astype(np.float32))
        labels = rng.integers(0, V, (T,))
        labels[::7] = -100          # ignore_index rows in every sub-tile
        labels = jnp.asarray(labels.astype(np.int32))

        def loss(hh, g):
            return fused_linear_cross_entropy(hh, w, labels, chunk_size=16,
                                              geometry=g)

        ref, ref_g = jax.value_and_grad(loss)(h, CEGeometry())
        for rows in (4, 8, 16):
            out, out_g = jax.value_and_grad(loss)(h, CEGeometry(rows=rows))
            _bitexact(ref, out)
            _bitexact(ref_g, out_g)   # bwd ignores the fwd-only sub-tile


class TestFlashGeometry:
    @pytest.fixture(autouse=True)
    def _interpret(self):
        os.environ["PT_FLASH_INTERPRET"] = "1"
        yield
        os.environ.pop("PT_FLASH_INTERPRET", None)

    def _qkv(self):
        rng = np.random.RandomState(4)
        mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
        return mk(1, 2, 256, 64), mk(1, 2, 256, 64), mk(1, 2, 256, 64)

    def test_block_q_sweep_gates_bitwise_per_chip(self):
        """block_q rows are independent — mathematically identical — but
        bitwise equality depends on the backend's matmul contracting each
        row the same way at every tile shape (host BLAS may regroup).
        The sweep decides EMPIRICALLY: every candidate is within fp
        tolerance of the default, any bitwise divergence is hard-rejected
        with the parity reason, and the winner's output is always
        bit-identical to the default's."""
        import sys
        fa = sys.modules["paddle_tpu.ops.flash_attention"]
        q, k, v = self._qkv()
        s = 1.0 / np.sqrt(64)
        outs = {}

        def measure(geom):
            cache = GeometryCache()
            cache.put("flash_attention", "float32", 64, local_device_kind(),
                      geom)
            install_geometry_cache(cache, source="swept")
            out, _ = fa._flash_fwd_bhsd(q, k, v, True, s)
            outs[geom.block_q] = np.asarray(out)
            return out, 1.0

        res = sweep_kernel_geometry(
            measure, "flash_attention", dtype="float32", key=64,
            candidates=[FlashAttentionGeometry(),
                        FlashAttentionGeometry(block_q=64),
                        FlashAttentionGeometry(block_q=128)])
        ref = outs[0]
        for t in res.trials:
            bq = t.geometry["block_q"]
            np.testing.assert_allclose(outs[bq], ref, rtol=2e-6, atol=2e-6)
            if not t.accepted:
                assert t.reject_reason == "parity_mismatch_vs_default"
                assert not np.array_equal(outs[bq], ref)
        # the winner's schedule reproduces the default bits exactly —
        # a regrouping candidate can never take the cell
        _bitexact(ref, outs[res.winner["block_q"]])
        assert res.trials[res.winner_index].exact

    def test_env_override_beats_cache(self):
        """PT_FLASH_BLOCKS stays the stronger knob: with it set the
        geometry seam must step aside entirely."""
        import sys
        fa = sys.modules["paddle_tpu.ops.flash_attention"]
        cache = GeometryCache()
        cache.put("flash_attention", "float32", 64, local_device_kind(),
                  FlashAttentionGeometry(block_q=64))
        install_geometry_cache(cache, source="swept")
        os.environ["PT_FLASH_BLOCKS"] = "128,128"
        try:
            q, _, _ = self._qkv()
            assert fa._geometry_blocks(q) == (None, None)
        finally:
            os.environ.pop("PT_FLASH_BLOCKS", None)

    def test_sweep_candidates_never_vary_block_kv(self):
        """block_kv regroups the online softmax — declared, honored when
        explicit, but NEVER a sweep candidate."""
        for g in geometry_candidates("flash_attention"):
            assert g.block_kv == 0


# ======================================================================
# candidate enumeration + cache semantics
# ======================================================================

class TestCandidates:
    @pytest.mark.parametrize("op", ["paged_attention", "fused_lora",
                                    "flash_attention", "fused_norm",
                                    "fused_ce"])
    def test_default_first_and_all_valid(self, op):
        cands = geometry_candidates(op)
        assert len(cands) >= 3
        assert cands[0] == default_geometry(op)
        for g in cands:
            g.validate()

    def test_quantized_paged_space_adds_dequant_axis(self):
        fp = geometry_candidates("paged_attention")
        q8 = geometry_candidates("paged_attention", quantized=True)
        assert all(g.dequant == "scores" for g in fp)
        assert any(g.dequant == "early" for g in q8)
        assert len(q8) > len(fp)

    def test_vmem_filter_keeps_default(self):
        tight = geometry_candidates("paged_attention",
                                    vmem_limit_bytes=1, head_dim=64,
                                    block_size=8, window=4, rep=4)
        assert tight[0] == default_geometry("paged_attention")

    def test_largest_divisor_clamps_onto_shape(self):
        assert _largest_divisor(6, 4) == 3
        assert _largest_divisor(5, 4) == 1
        assert _largest_divisor(32, 64) == 32
        assert _largest_divisor(32, 8) == 8


class TestGeometryCache:
    def _cache(self):
        c = GeometryCache()
        c.put("paged_attention", "int8", 128, "TPU v5e",
              PagedAttentionGeometry(kv_block_depth=2, dequant="early"))
        c.put("fused_norm", "float32", 2048, "TPU v5e",
              NormGeometry(rows=64))
        c.put("fused_lora", "float32", 8, "cpu",
              LoRAGeometry(rank_pad=16))
        return c

    def test_round_trip_and_fingerprint_stability(self):
        c = self._cache()
        back = GeometryCache.from_dict(c.to_dict())
        assert back == c and len(back) == 3
        assert back.fingerprint() == c.fingerprint()
        hit = back.lookup("paged_attention", "int8", 128, "TPU v5e")
        assert hit == PagedAttentionGeometry(kv_block_depth=2,
                                             dequant="early")

    def test_tampered_entry_fails_at_load(self):
        d = self._cache().to_dict()
        d["entries"]["fused_norm|float32|2048|TPU v5e"]["rows"] = 512
        with pytest.raises(ValueError, match="fingerprint"):
            GeometryCache.from_dict(d)
        with pytest.raises(ValueError, match="op|dtype|key|device_kind"):
            GeometryCache.from_dict({"entries": {"not-a-key": {}}})

    def test_unknown_chip_misses_to_default(self):
        install_geometry_cache(self._cache(), source="profile")
        geom, src = resolve_geometry("paged_attention", "int8", 128,
                                     device_kind="TPU v99")
        assert src == "default"
        assert geom == default_geometry("paged_attention")
        # same cell on the swept chip hits
        geom, src = resolve_geometry("paged_attention", "int8", 128,
                                     device_kind="TPU v5e")
        assert src == "profile" and geom.kv_block_depth == 2

    def test_put_rejects_wrong_family_and_invalid_geometry(self):
        c = GeometryCache()
        with pytest.raises(ValueError, match="PagedAttentionGeometry"):
            c.put("paged_attention", "float32", 64, "cpu",
                  NormGeometry(rows=8))
        with pytest.raises(ValueError, match="kv_block_depth"):
            c.put("paged_attention", "float32", 64, "cpu",
                  PagedAttentionGeometry(kv_block_depth=-1))

    def test_server_resolution_map(self):
        c = GeometryCache()
        kind = local_device_kind()
        c.put("paged_attention", "int8", 64, kind,
              PagedAttentionGeometry(dequant="early"))
        c.put("fused_lora", "float32", 8, kind, LoRAGeometry(rank_pad=8))
        install_geometry_cache(c, source="swept")
        got = resolve_server_geometries(head_dim=64, hidden=1024,
                                        dtype="float32", kv_quant="int8",
                                        lora_rank=8)
        # int8 KV routes the paged lookup through the int8 dtype key
        assert got["paged_attention"] == (
            PagedAttentionGeometry(dequant="early"), "swept")
        assert got["fused_lora"] == (LoRAGeometry(rank_pad=8), "swept")
        assert got["fused_norm"][1] == "default"
        no_lora = resolve_server_geometries(head_dim=64, hidden=1024,
                                            dtype="float32", kv_quant="none")
        assert "fused_lora" not in no_lora


# ======================================================================
# TunedProfile v3
# ======================================================================

def _profile(kernel_geometry=None):
    from paddle_tpu.autotune.space import ALL_KNOBS, ConfigSpace
    from paddle_tpu.autotune.workload import WorkloadSpec, draw_traffic
    from paddle_tpu.autotune.features import FeatureVector
    from paddle_tpu.autotune.profile import TunedProfile
    from paddle_tpu.cost_model import PagedTickCostModel

    space = ConfigSpace(ALL_KNOBS)
    cfg = space.default()
    wl = WorkloadSpec(requests=4, max_new=8)
    return TunedProfile(
        config=space.validate(cfg),
        config_fingerprint=space.fingerprint(cfg),
        workload=wl.to_dict(),
        workload_signature=draw_traffic(wl).signature(),
        metrics=FeatureVector().to_dict(),
        baseline=FeatureVector().to_dict(),
        search={"budget": 1, "seed": 0},
        cost_model=PagedTickCostModel().to_dict(),
        kernel_geometry=kernel_geometry)


class TestProfileV3:
    def test_round_trips_geometry_cache(self, tmp_path):
        from paddle_tpu.autotune.profile import TunedProfile

        c = GeometryCache()
        c.put("fused_ce", "float32", 2048, "TPU v5e", CEGeometry(rows=128))
        prof = _profile(kernel_geometry=c.to_dict())
        path = str(tmp_path / "tuned.json")
        prof.save(path)
        back = TunedProfile.load(path)
        assert back.kernel_geometry == prof.kernel_geometry
        assert back.geometry_cache() == c
        assert back.canonical_json() == prof.canonical_json()
        # a geometry-free profile parses to no cache
        assert _profile().geometry_cache() is None

    def test_v2_schema_refused(self):
        from paddle_tpu.autotune.profile import TunedProfile

        d = _profile().to_dict()
        d["schema"] = 2
        with pytest.raises(ValueError, match="retune"):
            TunedProfile.from_dict(d)

    def test_tampered_geometry_fails_at_load(self, tmp_path):
        from paddle_tpu.autotune.profile import TunedProfile

        c = GeometryCache()
        c.put("fused_ce", "float32", 2048, "TPU v5e", CEGeometry(rows=128))
        d = _profile(kernel_geometry=c.to_dict()).to_dict()
        d["kernel_geometry"]["entries"][
            "fused_ce|float32|2048|TPU v5e"]["rows"] = 64
        with pytest.raises(ValueError, match="fingerprint"):
            TunedProfile.from_dict(d)


# ======================================================================
# sweep determinism + parity hard-reject
# ======================================================================

class TestSweep:
    def _measure(self):
        """Injectable-clock stand-in: seconds are a pure function of the
        candidate, outputs are bitwise-identical EXCEPT rows=64 — the
        fastest candidate, which must be parity-rejected."""
        def measure(geom):
            secs = {0: 5.0, 8: 1.0, 64: 0.5, 256: 2.0, 512: 2.0}[geom.rows]
            out = np.full((4, 4), 7.0, np.float32)
            if geom.rows == 64:
                out = out + 1e-6
            return out, secs
        return measure

    def test_two_runs_identical_and_reject_never_wins(self):
        results = []
        for _ in range(2):
            cache = GeometryCache()
            res = sweep_kernel_geometry(self._measure(), "fused_norm",
                                        dtype="float32", key=2048,
                                        device_kind="TPU v5e", cache=cache)
            results.append(res)
            assert res.winner == {"rows": 8}
            assert res.speedup == pytest.approx(5.0)
            rejected = [t for t in res.trials if not t.accepted]
            assert [t.geometry["rows"] for t in rejected] == [64]
            assert all(t.reject_reason == "parity_mismatch_vs_default"
                       for t in rejected)
            assert cache.lookup("fused_norm", "float32", 2048,
                                "TPU v5e") == NormGeometry(rows=8)
        a, b = results
        assert [t.to_dict() for t in a.trials] \
            == [t.to_dict() for t in b.trials]
        assert (a.winner, a.winner_index, a.speedup) \
            == (b.winner, b.winner_index, b.speedup)

    def test_clock_tie_resolves_to_default(self):
        res = sweep_kernel_geometry(
            lambda g: (np.zeros(3, np.float32), 1.0), "fused_ce",
            dtype="float32", key=2048, device_kind="cpu")
        assert res.winner_index == 0
        assert res.winner == default_geometry("fused_ce").asdict()

    def test_max_candidates_truncates_by_proxy_keeping_default(self):
        seen = []
        res = sweep_kernel_geometry(
            lambda g: (seen.append(g.rows) or np.zeros(2, np.float32), 1.0),
            "fused_ce", dtype="float32", key=2048, device_kind="cpu",
            shape={"rows_total": 4096, "hidden": 2048},
            max_candidates=3)
        assert len(res.trials) == 3
        assert res.trials[0].geometry == default_geometry("fused_ce").asdict()
        assert len(seen) == 3


# ======================================================================
# serving: profile geometry end to end
# ======================================================================

def _tiny_model(layers=2, max_pos=160):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=layers, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=max_pos,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


def _tiny_cache():
    """Non-default winners keyed to the tiny model's cells (head_dim 16,
    hidden 64, float32) on this chip."""
    c = GeometryCache()
    kind = local_device_kind()
    c.put("paged_attention", "float32", 16, kind,
          PagedAttentionGeometry(kv_block_depth=2, q_rows=4))
    c.put("fused_norm", "float32", 64, kind, NormGeometry(rows=8))
    c.put("fused_ce", "float32", 64, kind, CEGeometry(rows=8))
    return c


@pytest.mark.slow
def test_profile_geometry_zero_steady_state_recompiles():
    """A server built from a v3 profile resolves per-layer geometry at
    construction (source 'profile'), serves token-identically to a
    default-geometry twin, and holds the steady state compile-free —
    geometry is trace-time, so one warm pass covers every later tick."""
    from paddle_tpu.analysis import jit_cache_guard
    from paddle_tpu.inference.serving import GenerationServer

    model, cfg = _tiny_model()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).tolist()
               for n in (5, 12, 7)]

    ref_srv = GenerationServer(model, max_batch=2, max_len=64, cache="paged",
                               block_size=4, prefill_chunk=8)
    assert all(src == "default"
               for _, src in ref_srv.kernel_geometry.values())
    rids = [ref_srv.submit(p, max_new_tokens=6) for p in prompts]
    got = ref_srv.run()
    ref_out = [got[r] for r in rids]

    prof = _profile(kernel_geometry=_tiny_cache().to_dict())
    srv = GenerationServer(model, max_batch=2, max_len=64, cache="paged",
                           block_size=4, prefill_chunk=8, profile=prof)
    assert srv.kernel_geometry["paged_attention"][1] == "profile"
    assert srv.kernel_geometry["fused_norm"][1] == "profile"
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    got = srv.run()                       # warm: traces every program once
    assert [got[r] for r in rids] == ref_out, \
        "profile geometry changed the served tokens"

    rids = [srv.submit(rng.randint(1, cfg.vocab_size, (n,)).tolist(),
                       max_new_tokens=6) for n in (9, 3)]
    with jit_cache_guard("profile-geometry steady state") as g:
        out = srv.run()
    assert g.compiles == 0
    assert all(len(out[r]) > 0 for r in rids)

    # satellite: the info gauge labels which schedule actually ran
    srv.telemetry_snapshot()
    gauge = srv.telemetry.registry.get("serving_kernel_geometry")
    assert gauge.value(op="paged_attention", source="profile") == 1.0
    assert gauge.value(op="flash_attention", source="default") == 1.0


@pytest.mark.slow
def test_snapshot_refuses_cross_geometry_restore():
    """kernel geometry is trace-time schedule state: a snapshot stamps
    the non-default map and restores only into a server resolving the
    same winners — while pre-geometry snapshots (no key) stay legal for
    all-default servers."""
    from paddle_tpu.inference.serving import GenerationServer

    model, _ = _tiny_model()
    a = GenerationServer(model, max_len=64, cache="paged", block_size=4)
    a.submit([1, 2, 3], max_new_tokens=4)
    a.run()
    snap = a.snapshot()
    assert snap["config"].get("kernel_geometry") is None

    install_geometry_cache(_tiny_cache(), source="swept")
    b = GenerationServer(model, max_len=64, cache="paged", block_size=4)
    assert b.kernel_geometry["paged_attention"][1] == "swept"
    with pytest.raises(ValueError, match="kernel_geometry"):
        b.restore(snap)

    b.submit([4, 5], max_new_tokens=4)
    b.run()
    snap_b = b.snapshot()
    install_geometry_cache(None)
    c = GenerationServer(model, max_len=64, cache="paged", block_size=4)
    with pytest.raises(ValueError, match="kernel_geometry"):
        c.restore(snap_b)

    # a pre-geometry snapshot (config without the key) restores into an
    # all-default server: None == None under the fingerprint walk
    legacy = {k: v for k, v in snap["config"].items()
              if k != "kernel_geometry"}
    import copy
    old = copy.deepcopy(snap)
    old["config"] = legacy
    d = GenerationServer(model, max_len=64, cache="paged", block_size=4)
    d.restore(old)
