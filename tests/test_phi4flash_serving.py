"""Phi-4-mini-flash (SambaY) through the serving path, at a tiny preset, against
the benchmark's plain reference (``benchmarks/reference/sambay_lm.py``: float32,
no cache, nothing imported from ``paddle_tpu``).

The preset has all five layer kinds (Mamba, window, full, GMU, cross), one
period of each decoder, and a window (24) shorter than the sequences. Weights
are the benchmark's seeded draws at ``initializer_range`` 0.15 (at 0.02 a
64-wide network's logits are all but flat and any arithmetic would pass).

Tolerances. Program and reference both compute in float32 here (x64 off in
the model, "highest" matmuls under the test config), so they differ by
summation order only: logits agree to ``2e-4`` absolute (observed ~2e-5;
logits are O(1)). The controls say what that tolerance catches: the
reference with its SSM state rounded to bfloat16 is off by > 2e-3, and a
model without the ``(1 - lambda_init)`` factor or without the sub-norm by
> 1e-2 — each at least ten times the tolerance.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.cache_spec import CacheSpecError
from paddle_tpu.jit import functional_call
from paddle_tpu.ops import select

from benchmarks.drivers import serve_hybrid
from benchmarks.reference import sambay_lm as ref


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # process-wide, and earlier test files may have left it pinned
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)

TOL = 2e-4
TINY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": 24, "mb_per_layer": 2,
    "layer_norm_eps": 1e-5, "max_position_embeddings": 4096,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "initializer_range": 0.15,
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 4},
}
BS, CHUNK = 8, 16


@pytest.fixture(scope="module")
def built():
    model, weights = serve_hybrid.build_model(TINY, seed=11)
    return model, weights


def _server(model, **kw):
    kw = {"max_batch": 3, "max_len": 192, "block_size": BS,
          "prefill_chunk": CHUNK, **kw}
    return GenerationServer(model, cache="paged", **kw)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).tolist()


def _teacher_forced_logits(model, seq, n_prefill, slot=1):
    """Logits of the SERVING programs at positions ``n_prefill-1 ..
    len(seq)-2``: chunked prefill of ``seq[:n_prefill]`` (logits of its last
    token), then one paged decode step per further token of ``seq``, fed the
    known token — every program the server dispatches, minus its sampling."""
    srv = _server(model)
    ex, params = srv._exec, srv.params
    table = np.zeros((srv._table_width,), np.int32)
    need = -(-len(seq) // BS)
    table[:need] = [srv.alloc.alloc() for _ in range(need)]
    flat, slot_p = list(ex.pools), list(ex.slot_pools)
    out = []
    for start in range(0, n_prefill, CHUNK):
        end = min(start + CHUNK, n_prefill)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :end - start] = seq[start:end]
        lg, flat, slot_p = ex._chunk_prefill_fn(
            params, jnp.asarray(chunk), flat, jnp.asarray(table),
            jnp.int32(start), jnp.int32(end - start - 1), None, (), slot_p,
            jnp.asarray([slot, end - start, end == n_prefill], jnp.int32))
    out.append(np.asarray(lg[0]))
    B = srv.max_batch
    active = np.zeros((B,), np.int32)
    active[slot] = 1
    bt = np.zeros((B, srv._table_width), np.int32)
    bt[slot] = table
    for p in range(n_prefill, len(seq) - 1):
        toks = np.zeros((B, 1), np.int32)
        toks[slot] = seq[p]
        pos = np.zeros((B,), np.int32)
        pos[slot] = p

        def call():
            h, new = model.model.paged_decode_step(
                Tensor(jnp.asarray(toks)), ex._pool_views(flat, slot_p),
                jnp.asarray(bt), jnp.asarray(pos),
                active=jnp.asarray(active))
            return srv._head(h), new

        lg, new = functional_call(model, params, call_fn=call)
        flat, slot_p = ex._flat_pools(new)
        out.append(np.asarray(lg.value[slot, 0]))
    return np.stack(out)


def test_cache_free_forward_matches_the_reference(built):
    model, weights = built
    seq = _tokens(70, 1)
    got = np.asarray(model(Tensor(jnp.asarray([seq]))).value[0])
    want = ref.logits_at(weights, TINY, seq, list(range(len(seq))))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n_prefill", [
    pytest.param(41, id="prompt-ends-inside-a-chunk-past-the-window"),
    pytest.param(32, id="prompt-ends-on-a-chunk-boundary"),
    pytest.param(5, id="prompt-shorter-than-a-block"),
])
def test_chunked_prefill_then_paged_decode_logits_match_the_reference(
        built, n_prefill):
    model, weights = built
    seq = _tokens(n_prefill + 40, 2)
    got = _teacher_forced_logits(model, seq, n_prefill)
    pos = list(range(n_prefill - 1, len(seq) - 1))
    want = ref.logits_at(weights, TINY, seq, pos)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_the_tolerance_catches_a_bf16_state_and_a_dropped_norm(built):
    model, weights = built
    seq = _tokens(80, 3)
    pos = list(range(len(seq)))
    want = ref.logits_at(weights, TINY, seq, pos)
    low = ref.logits_at(weights, TINY, seq, pos, mode="state_bf16")
    assert np.abs(low - want).max() > 10 * TOL
    # the program without (1 - lambda_init), then without the sub-norm
    from paddle_tpu.models import phi4flash as m

    x = Tensor(jnp.asarray([seq]))
    orig = m._diff_combine
    try:
        m._diff_combine = lambda o, lam, lam0, w, eps: orig(o, lam, 0.0, w,
                                                            eps)
        no_scale = np.asarray(model(x).value[0])
        m._diff_combine = lambda o, lam, lam0, w, eps: orig(
            o, lam, lam0, w, 1e30) * 1e15
        no_norm = np.asarray(model(x).value[0])
    finally:
        m._diff_combine = orig
    assert np.abs(no_scale - want).max() > 50 * TOL
    assert np.abs(no_norm - want).max() > 50 * TOL


def _gaps(weights, prompt, served):
    return ref.served_gaps(weights, TINY, prompt, served)[0]


def test_two_requests_of_very_different_lengths_in_one_batch(built):
    model, weights = built
    prompts = [_tokens(90, 4), _tokens(3, 5)]
    srv = _server(model)
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (60, 45))]
    out = srv.run()
    for p, r in zip(prompts, rids):
        served = out[r][len(p):]
        assert out[r][:len(p)] == p
        assert _gaps(weights, p, served).max() < TOL
        fixture = np.asarray(model.generate(
            jnp.asarray([p]), max_new_tokens=len(served)).value)[0]
        assert out[r] == fixture.tolist()
    assert srv.assert_conserved()


def test_preemption_and_readmission_carry_the_state(built):
    """A decoding request is swapped out past the window (its blocks, its
    window rings and its SSM state go to the host), another request takes
    its slot's neighbours, and it resumes in ANOTHER slot: same tokens as
    the undisturbed run, and still the reference's."""
    model, weights = built
    prompt = _tokens(50, 6)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=50)
    want = calm.run()[r0]

    srv = _server(model, telemetry=True)
    rid = srv.submit(prompt, max_new_tokens=50)
    while len(srv._slots[0].generated if srv._slots[0] else []) < 12:
        srv.step()
    assert srv._preempt_slot(0)
    saves = srv.telemetry.registry.counter("serving_state_saves")
    assert saves.total() == 1
    # zero what the slot held: a resume that read stale device state would
    # pass by accident
    srv._exec.restore_slot(0, [np.zeros_like(a)
                               for a in srv._exec.save_slot(0)])
    other = srv.submit(_tokens(20, 7), max_new_tokens=30)   # takes slot 0
    out = srv.run()
    assert out[rid] == want
    assert _gaps(weights, prompt, out[rid][len(prompt):]).max() < TOL
    assert len(out[other]) == 50
    names = {s["name"] for s in srv.telemetry.tracer.spans()
             if s["rid"] == rid}
    assert {"state_save", "state_restore", "swap_out", "swap_in"} <= names
    assert srv.assert_conserved()


def test_snapshot_and_restore_carry_the_state(built):
    model, _ = built
    prompt = _tokens(40, 8)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=40)
    want = calm.run()[r0]
    srv = _server(model)
    rid = srv.submit(prompt, max_new_tokens=40)
    for _ in range(12):
        srv.step()
    snap = srv.evacuate()
    assert snap["requests"][0]["kv"]["n_extra"] == len(
        srv._exec.slot_pools)
    fresh = _server(model)
    fresh.restore(snap)
    assert fresh.run()[rid] == want


def test_prefix_sharing_is_off_and_unsupported_features_are_named(built):
    model, _ = built
    srv = _server(model)
    shared = _tokens(48, 9)
    for tail in (1, 2):
        srv.submit(shared + [tail], max_new_tokens=4)
    srv.run()
    st = srv.kv_stats()
    assert st["prefix_hit_blocks"] == 0 and st["blocks_cached"] == 0
    from paddle_tpu.inference.speculative import SpecConfig

    for kw in ({"kv_quant": "int8"}, {"spec": SpecConfig(k=2)},
               {"mesh": "tp=2"}):
        with pytest.raises(CacheSpecError, match="per-slot state"):
            _server(model, **kw)
    with pytest.raises(CacheSpecError, match="cache='dense'"):
        GenerationServer(model, cache="dense", max_batch=2, max_len=64)


def test_bytes_allotted_per_slot_by_kind_equal_the_closed_form(built):
    """Window: at most the window plus one block of K and V per window
    layer, whatever the length; state: fixed; full: whole blocks in
    proportion to the length — as a request grows to 4 x the window."""
    model, _ = built
    srv = _server(model, max_batch=2)
    spec = srv.cache_spec
    cfg = model.cfg
    W, G, D = cfg.sliding_window, cfg.num_key_value_heads // 2, \
        2 * cfg.head_dim
    n_win, n_mamba = len(spec.of_kind("window")), len(spec.of_kind("state"))
    ring_tokens = (-(-W // BS) + 1) * BS
    assert ring_tokens <= W + BS
    window_bytes = n_win * 2 * ring_tokens * G * D * 4
    state_bytes = n_mamba * ((cfg.d_conv - 1) * cfg.d_inner * 4
                             + cfg.d_state * cfg.d_inner * 4)
    full_per_block = 2 * BS * G * D * 4           # one full layer, K and V
    assert spec.slot_bytes(BS) == {"window": window_bytes,
                                   "state": state_bytes}
    assert spec.block_bytes(BS) == full_per_block
    # the executor's arrays are what the spec says
    ex = srv._exec
    assert sum(p.nbytes for p in ex.pools) == \
        srv.alloc.num_blocks * full_per_block
    rings = sum(p.nbytes for i in spec.of_kind("window")
                for j in ex._slot_index[i] for p in [ex.slot_pools[j]])
    assert rings == window_bytes * srv.max_batch + n_win * 2 * BS * G * D * 4
    states = sum(p.nbytes for i in spec.of_kind("state")
                 for j in ex._slot_index[i] for p in [ex.slot_pools[j]])
    assert states == state_bytes * srv.max_batch

    srv.submit(_tokens(10, 10), max_new_tokens=4 * W)
    seen = set()
    while srv.step():
        if srv._slots[0] is None or srv._prefilling[0]:
            continue
        n = int(srv.pos[0])
        b = srv.cache_bytes()
        assert b["cache_bytes_window"] == window_bytes
        assert b["cache_bytes_state"] == state_bytes
        assert b["cache_bytes_full"] == -(-n // BS) * full_per_block \
            or b["cache_bytes_full"] == -(-(n + 1) // BS) * full_per_block
        assert b["cache_bytes_full"] == \
            len(srv._slots[0].table) * spec.block_bytes(BS)
        seen.add(n)
    assert max(seen) >= 4 * W


def test_window_and_shared_context_counters(built):
    model, _ = built
    srv = _server(model)
    n, new = 30, 20
    srv.submit(_tokens(n, 12), max_new_tokens=new)
    srv.run()
    reg = srv.telemetry.registry
    ctxs = range(n + 1, n + new)            # decode rows: tokens 2..new
    assert reg.counter("serving_decode_rows").total() == len(ctxs)
    assert reg.counter("serving_decode_ctx_shared").total() == sum(ctxs)
    assert reg.counter("serving_decode_ctx_window").total() == sum(
        min(c, TINY["sliding_window"]) for c in ctxs)


def test_a_dense_decoder_is_the_all_full_case_of_the_spec():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config

    model = LlamaForCausalLM(llama_tiny_config())
    srv = GenerationServer(model, cache="paged", max_batch=2, max_len=64)
    spec = srv.cache_spec
    assert {l.kind for l in spec.layers} == {"full"}
    assert not spec.has_slot_state and srv._exec.slot_pools == []
    assert len(srv._exec.pools) == 2 * model.cfg.num_hidden_layers
    b = srv.cache_bytes()
    assert b["cache_bytes_window_allotted"] == 0 and b["state_slots"] == 0
    reg = srv.telemetry.registry
    srv.submit([1, 2, 3], max_new_tokens=4)
    srv.run()
    assert reg.counter("serving_decode_ctx_window").total() == 0
    assert reg.counter("serving_decode_ctx_shared").total() == 0
