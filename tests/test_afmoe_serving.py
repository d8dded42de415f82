"""Trinity (``afmoe``: window and full attention layers, gated and QK-normed;
two dense layers, then one chip's share of a many-expert layer) through the
serving path, at a tiny preset, against the benchmark's plain reference
(``benchmarks/reference/window_moe_lm.py``: float32, a full score matrix
under a window mask, the held experts on their routed rows, no cache and no
ring, nothing imported from ``paddle_tpu``; it routes for itself).

The preset: hidden 64, 4 query and 2 KV heads of 16, window 24 (a ring of
three blocks of 8 and one more), the published pattern S S S F S S S F with
layers 0-1 dense (width 96), a router over 16 experts of which the top 4,
experts 0-7 held here, width 32, a shared expert, ``route_scale`` 2.826, a
vocabulary of 256. Weights are the benchmark's seeded draws at
``initializer_range`` 0.3 (every branch is normed before it is added, so
the width sets how far from flat the router and the head are, not the
scale of the stream).

Tolerances. In float32 program and reference differ by summation order
(the program's online softmax, grouped expert products, chunked writes):
logits agree to ``TOL`` = 1e-4 absolute (logits are O(1)). What that catches
is held by the controls: every planted fault of the reference and its
bfloat16 arithmetic are each off by more than 10 x TOL.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.cache_spec import CacheSpecError
from paddle_tpu.jit import functional_call
from paddle_tpu.ops import select

from benchmarks.drivers import serve_window_moe as drv
from benchmarks.reference import window_moe_lm as ref
from tests.test_fused_tick import _drive, _partitioned as _chunks
from tests.test_fused_tick import _server as _fused_tick_server


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # process-wide, and earlier test files may have left it pinned
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


TOL = 1e-4
PATTERN = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "num_dense_layers": 2, "layer_types": PATTERN * 3,
    "global_attn_every_n_layers": 4, "sliding_window": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000, "num_experts": 8, "experts_held": [0, 8],
    "published": {"num_experts": 16}, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "n_group": 1, "topk_group": 1,
    "mup_enabled": True, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "torch_dtype": "float32", "initializer_range": 0.3,
    "router_bias_range": 0.03,
}
BS, CHUNK = 8, 16
WINDOW_LAYERS, FULL_LAYERS = 6, 2


@pytest.fixture(scope="module")
def built():
    return drv.build_model(TINY, seed=11)


def _server(model, **kw):
    kw = {"max_batch": 3, "max_len": 192, "block_size": BS,
          "prefill_chunk": CHUNK, **kw}
    return GenerationServer(model, cache="paged", **kw)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).tolist()


def _count(srv, name, **where):
    c = srv.telemetry.registry.counter(name)
    return int(c.value(**where) if where else c.total())


def _gaps(weights, prompt, served):
    return ref.served_gaps(weights, TINY, prompt, served)[0]


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
        lg, flat, slot_p, _ = ex._chunk_prefill_fn(
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
            model.model.take_step_stats()
            return srv._head(h), new

        lg, new = functional_call(model, params, call_fn=call)
        flat, slot_p = ex._flat_pools(new)
        out.append(np.asarray(lg.value[slot, 0]))
    return np.stack(out)


# ------------------------------------------------------- (a) against the reference
def test_cache_free_forward_matches_the_reference(built):
    model, weights = built
    seq = _tokens(70, 1)
    got = np.asarray(model(Tensor(jnp.asarray([seq]))).value[0])
    want = ref.logits_at(weights, TINY, seq, list(range(len(seq))))
    assert want.std() > 0.3                   # 3,000 x TOL: not flat
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n_prefill", [
    pytest.param(41, id="ring_wrapped_by_the_prompt_chunk_boundaries_inside"),
    pytest.param(32, id="prompt_ends_on_a_chunk_boundary"),
    pytest.param(5, id="prompt_shorter_than_a_block"),
])
def test_chunked_prefill_then_paged_decode_logits_match_the_reference(
        built, n_prefill):
    """Prefill in chunks of 16 (a chunk past position 24 attends a ring's
    past beside itself, and every chunk boundary after it falls inside a
    window), then decode through the rings and the pool until each ring
    (4 blocks of 8) has wrapped more than once, against the reference's
    full forward."""
    model, weights = built
    seq = _tokens(n_prefill + 72, 2)
    got = _teacher_forced_logits(model, seq, n_prefill)
    pos = list(range(n_prefill - 1, len(seq) - 1))
    want = ref.logits_at(weights, TINY, seq, pos)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_served_tokens_through_generation_server_are_the_references(built):
    """Two requests of very different lengths in one batch, chunks riding
    in the decode call: every served token is the reference's argmax."""
    model, weights = built
    prompts = [_tokens(90, 14), _tokens(3, 15)]
    srv = _server(model)
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (60, 45))]
    out = srv.run()
    for p, r, n in zip(prompts, rids, (60, 45)):
        assert out[r][:len(p)] == p and len(out[r]) == len(p) + n
        assert _gaps(weights, p, out[r][len(p):]).max() < TOL
    assert srv._exec.chunk_alone_why is None
    assert srv.assert_conserved()


@pytest.mark.parametrize("mode", ["bf16"] + list(ref.FAULTS))
def test_the_tolerance_catches(built, mode):
    _, weights = built
    seq = _tokens(80, 6)
    pos = list(range(len(seq)))
    want = ref.logits_at(weights, TINY, seq, pos)
    low = ref.logits_at(weights, TINY, seq, pos, mode=mode)
    assert np.abs(low - want).max() > 10 * TOL


def test_bfloat16_serving_stays_within_the_bfloat16_control():
    cfg = dict(TINY, torch_dtype="bfloat16")
    model, weights = drv.build_model(cfg, seed=12)
    seq = _tokens(60, 7)
    got = _teacher_forced_logits(model, seq, 37)
    pos = list(range(36, len(seq) - 1))
    want = ref.logits_at(weights, cfg, seq, pos)
    control = ref.logits_at(weights, cfg, seq, pos, mode="bf16")
    assert np.abs(got - want).max() < 3 * np.abs(control - want).max()


# ------------------------------------------------------------- (b) the shares
def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Router over 16, top 4; two chips hold experts 0-7 and 8-15. The
    routed parts of the two shares plus the shared expert counted once =
    the uncut reference's expert layer, for the program's layer and for the
    reference's own share alike."""
    from paddle_tpu.models.afmoe import AfmoeDecoderLayer, afmoe_tiny_config

    whole = dict(TINY, num_experts=16, experts_held=[0, 16])
    z = ref.sizes(whole)
    rng = np.random.default_rng(8)
    w = {k: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for k, s in {"router": (64, 16), "router_bias": (16,),
                      "w_gate_e": (16, 64, 32), "w_up_e": (16, 64, 32),
                      "w_down_e": (16, 32, 64), "ws_gate": (64, 32),
                      "ws_up": (64, 32), "ws_down": (32, 64)}.items()}
    h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    want = np.asarray(ref.expert_part(h, w, z))
    # what every chip computes alike, counted once
    shared = np.asarray(ref._swiglu(h, w["ws_gate"], w["ws_up"],
                                    w["ws_down"], "f32"))
    ref_sum, got_sum, pairs = shared.copy(), shared.copy(), 0
    for lo in (0, 8):
        mine = dict(w, **{k: w[k][lo:lo + 8]
                          for k in ("w_gate_e", "w_up_e", "w_down_e")})
        ref_sum += np.asarray(ref.expert_part(h, mine, z, held=(lo, lo + 8),
                                              with_shared=False))
        layer = AfmoeDecoderLayer(afmoe_tiny_config(
            experts_held=(lo, lo + 8)), 2).mlp
        layer.router.weight.set_value(w["router"])
        layer.router_bias.set_value(w["router_bias"])
        layer.experts_gate.set_value(w["w_gate_e"][lo:lo + 8])
        layer.experts_up.set_value(w["w_up_e"][lo:lo + 8])
        layer.experts_down.set_value(w["w_down_e"][lo:lo + 8])
        part, counts = layer.routed(h)
        got_sum = got_sum + np.asarray(part)
        assert int(counts.sum()) == 24 * 4          # held here + elsewhere
        pairs += int(counts[:8].sum())
    assert pairs == 24 * 4                           # every pair held once
    assert np.abs(ref_sum - want).max() < 1e-4
    assert np.abs(got_sum - want).max() < 1e-4


def test_the_layers_are_dense_then_experts_and_window_then_full():
    from paddle_tpu.models.afmoe import AfmoeConfig

    model, _ = drv.build_model(dict(TINY, num_hidden_layers=4), seed=3)
    layers = list(model.model.layers)
    assert [l.dense_mlp for l in layers] == [True, True, False, False]
    assert [l.kind for l in layers] == ["window"] * 3 + ["full"]
    assert [l.self_attn.window for l in layers] == [24, 24, 24, 0]
    pub = AfmoeConfig()
    assert [i for i in range(32) if pub.kind(i) == "full"] == list(
        range(3, 32, 4))
    for kw, why in (({"score_func": "softmax"}, "sigmoid"),
                    ({"n_group": 2}, "group"),
                    ({"tie_word_embeddings": True}, "untied"),
                    ({"layer_types": ("sliding_attention", "mamba")},
                     "layer_types")):
        with pytest.raises(ValueError, match=why):
            AfmoeConfig(num_hidden_layers=2, **kw)


# ------------------------------------------------ (c) a chunk rides in the tick
def _joint_operands(srv, seq, slot, rows, chunk_no):
    """The slots in ``rows`` hold a request of 30 tokens each (its ring
    wrapped) and are about to decode their next token; ``slot`` has had
    ``chunk_no`` chunks of ``seq`` and is about to take the next. Returns
    (flat pools, slot pools, (tokens, tables, pos), chunk operands)."""
    ex, params = srv._exec, srv.params
    flat, slot_p = list(ex.pools), list(ex.slot_pools)
    W, B = srv._table_width, srv.max_batch
    tables = np.zeros((B, W), np.int32)

    def prefill(s, toks, upto):
        nonlocal flat, slot_p
        need = -(-len(toks) // BS)
        tables[s, :need] = [srv.alloc.alloc() for _ in range(need)]
        for start in range(0, upto, CHUNK):
            end = min(start + CHUNK, upto)
            chunk = np.zeros((1, CHUNK), np.int32)
            chunk[0, :end - start] = toks[start:end]
            _, flat, slot_p, _ = ex._chunk_prefill_fn(
                params, jnp.asarray(chunk), flat, jnp.asarray(tables[s]),
                jnp.int32(start), jnp.int32(end - start - 1), None, (),
                slot_p, jnp.asarray([s, end - start, end == len(toks)],
                                    jnp.int32))

    tokens, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    for s in rows:
        mine = _tokens(31, 40 + s)
        prefill(s, mine, 30)
        tokens[s], pos[s] = mine[30], 30
    prefill(slot, seq, chunk_no * CHUNK)
    start = chunk_no * CHUNK
    end = min(start + CHUNK, len(seq))
    chunk = np.zeros((1, CHUNK), np.int32)
    chunk[0, :end - start] = seq[start:end]
    return flat, slot_p, (tokens, tables, pos), (
        jnp.asarray(chunk), jnp.asarray(tables[slot]), jnp.int32(start),
        jnp.int32(end - start - 1),
        jnp.asarray([slot, end - start, end == len(seq)], jnp.int32))


@pytest.mark.parametrize("rows,chunk_no", [
    ((0, 2), 2), ((0, 2), 0), ((2,), 3), ((), 1)],
    ids=["a_chunk_past_the_window", "first_chunk", "final_partial_chunk",
         "every_decode_row_masked"])
def test_the_joint_step_is_the_two_steps_one_after_the_other(built, rows,
                                                            chunk_no):
    """``paged_decode_chunk_step`` over B + C rows against
    ``paged_decode_step`` and then ``paged_prefill_chunk`` on the same
    views: the decode rows' hidden states, the chunk's row at ``last_idx``,
    the full layers' pool and every slot's rings. A slot that neither
    decodes nor prefills keeps its rings bit for bit."""
    model, _ = built
    srv = _server(model)
    ex, params = srv._exec, srv.params
    seq = _tokens(57, 30)                       # 16 + 16 + 16 + 9
    flat, slot_p, (tokens, tables, pos), ck = _joint_operands(
        srv, seq, 1, rows, chunk_no)
    chunk, table, start, last_idx, triple = ck
    B = srv.max_batch
    active = np.zeros((B,), np.int32)
    active[list(rows)] = 1
    bt = np.where(active[:, None] > 0, tables, 0)
    act, btv, posv = (jnp.asarray(active), jnp.asarray(bt),
                      jnp.asarray(pos * active))

    def two_steps():
        h_rows, new = model.model.paged_decode_step(
            Tensor(jnp.asarray(tokens)[:, None]),
            ex._pool_views(flat, slot_p), btv, posv, active=act)
        h_last, new = model.model.paged_prefill_chunk(
            Tensor(chunk), new, table, start, last_idx=last_idx, slot=triple)
        model.model.take_step_stats()
        return jnp.concatenate([h_rows.value[:, 0], h_last.value[0]]), new

    def joint():
        ids = jnp.concatenate([jnp.asarray(tokens)[None], chunk], axis=1)
        h, new = model.model.paged_decode_chunk_step(
            Tensor(ids), ex._pool_views(flat, slot_p), btv, posv, table,
            start, last_idx, active=act, slot=triple)
        model.model.take_step_stats()
        return h.value[0], new

    want_h, want = functional_call(model, params, call_fn=two_steps)
    got_h, got = functional_call(model, params, call_fn=joint)
    keep = list(rows) + [B]
    np.testing.assert_allclose(np.asarray(got_h)[keep],
                               np.asarray(want_h)[keep], rtol=2e-5,
                               atol=2e-5)
    (wf, ws), (gf, gs) = ex._flat_pools(want), ex._flat_pools(got)
    spec = srv.cache_spec
    R = spec.ring_blocks(0, BS)
    for a, b in zip(gf + gs, wf + ws):
        # (block 0 is the masked rows' scratch: nobody reads it)
        np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:],
                                   rtol=2e-5, atol=2e-5)
    for a, was in zip(gs, slot_p):
        a, was = np.asarray(a), np.asarray(was)
        for s in range(B):
            ring = slice(1 + s * R, 1 + (s + 1) * R)
            if s != 1 and s not in rows:
                np.testing.assert_array_equal(a[ring], was[ring])
        assert np.abs(a[1 + R:1 + 2 * R] - was[1 + R:1 + 2 * R]).max() > 0


MEETS = [(0, _tokens(9, 31), 30), (2, _tokens(40, 32), 20),
         (3, _tokens(21, 33), 24), (9, _tokens(70, 34), 12)]
TOGETHER = [(0, _tokens(12, 35), 40), (3, _tokens(37, 36), 10),
            (3, _tokens(50, 37), 10)]


@pytest.mark.parametrize("case", ["a_chunk_meets_decoding_rows",
                                  "two_prefilling_slots_in_one_tick"])
def test_chunks_that_ride_serve_the_references_tokens(built, case):
    """Token for token what the two-program path serves, and the
    reference's; chunks ride (``fused``), never ``slot_state``."""
    model, weights = built
    arrivals = {"a_chunk_meets_decoding_rows": MEETS,
                "two_prefilling_slots_in_one_tick": TOGETHER}[case]
    ref_srv = _fused_tick_server(model, two_programs=True, **{
        "max_batch": 4, "max_len": 192, "block_size": BS,
        "prefill_chunk": CHUNK})
    want = _drive(ref_srv, arrivals)
    srv = _server(model, max_batch=4)
    got = _drive(srv, arrivals, check=lambda s, _: s.assert_conserved())
    assert got == want
    for i, (_, prompt, new) in enumerate(arrivals):
        assert _gaps(weights, prompt, got[i][len(prompt):]).max() < TOL
    total, fused, alone = _chunks(srv)
    assert fused >= 3 and set(alone) <= {"no_decoding_row", "second_chunk"}


# ---------------------------------------- (d) the rings travel with a request
def test_preemption_swap_and_snapshot_carry_the_rings(built):
    """A decoding request past its window is swapped out (its blocks and
    its rings go to the host), another request takes its slot, and it
    resumes: same tokens as the undisturbed run, and the reference's. A
    snapshot taken mid-request restores onto a fresh server the same."""
    model, weights = built
    prompt = _tokens(50, 16)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=40)
    want = calm.run()[r0]
    assert _gaps(weights, prompt, want[len(prompt):]).max() < TOL

    srv = _server(model, telemetry=True)
    rid = srv.submit(prompt, max_new_tokens=40)
    while len(srv._slots[0].generated if srv._slots[0] else []) < 12:
        srv.step()
    assert srv._preempt_slot(0)
    assert _count(srv, "serving_state_saves") == 1
    # zero what the slot held: a resume that read stale rings would pass
    # by accident
    srv._exec.restore_slot(0, [np.zeros_like(a)
                               for a in srv._exec.save_slot(0)])
    other = srv.submit(_tokens(20, 17), max_new_tokens=30)   # takes slot 0
    out = srv.run()
    assert out[rid] == want and len(out[other]) == 50
    names = {s["name"] for s in srv.telemetry.tracer.spans()
             if s["rid"] == rid}
    assert {"state_save", "state_restore", "swap_out", "swap_in"} <= names
    assert srv.assert_conserved()

    srv = _server(model)
    rid = srv.submit(prompt, max_new_tokens=40)
    for _ in range(14):
        srv.step()
    snap = srv.evacuate()
    assert snap["requests"][0]["kv"]["n_extra"] == len(srv._exec.slot_pools)
    fresh = _server(model)
    fresh.restore(snap)
    assert fresh.run()[rid] == want


def test_unsupported_features_are_refused_by_name(built):
    model, _ = built
    from paddle_tpu.inference.speculative import SpecConfig

    for kw in ({"kv_quant": "int8"}, {"spec": SpecConfig(k=2)},
               {"mesh": "tp=2"}, {"mesh": "cp=2"}):
        with pytest.raises(CacheSpecError, match="per-slot state"):
            _server(model, **kw)
    with pytest.raises(CacheSpecError, match="lora="):
        _server(model, lora=object())


# ------------------------------------------------------------- (e) the bytes
def test_cache_bytes_equal_the_closed_form(built):
    model, _ = built
    srv = _server(model, max_batch=2)
    spec = srv.cache_spec
    assert [l.kind for l in spec.layers] == (["window"] * 3 + ["full"]) * 2
    assert {l.layout for l in spec.layers} == {"head"}
    ring = WINDOW_LAYERS * 2 * (24 // BS + 1) * BS * 2 * 16 * 4
    assert spec.slot_bytes(BS) == {"window": ring, "state": 0}
    per_block = FULL_LAYERS * 2 * BS * 2 * 16 * 4
    assert spec.block_bytes(BS) == per_block
    ex = srv._exec
    assert sum(p.nbytes for p in ex.pools) == srv.alloc.num_blocks * per_block
    # the rings of every slot and the scratch block of each pool
    assert sum(p.nbytes for p in ex.slot_pools) == ring * srv.max_batch + (
        WINDOW_LAYERS * 2 * BS * 2 * 16 * 4)


def test_the_published_cut_has_the_bytes_the_issue_reckons():
    """The closed forms of the benchmark configuration: parameters of chip
    0's share and the bytes of a slot's window rings."""
    from benchmarks.weights_window_moe import n_params, window_moe_shapes
    from paddle_tpu.models.afmoe import AfmoeForCausalLM

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "trinity-mini-l8-ep2.json")) as f:
        cfg = json.load(f)
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128     # q g o, k v, norms
    assert attn == 27_263_232
    norms = 4 * 2048
    dense = attn + norms + 3 * 2048 * 6144
    expert = attn + norms + 2048 * 128 + 128 + 3 * 2048 * 1024 * 65
    assert (dense, expert) == (65_020_160, 436_478_336)
    ends = 2 * 100_096 * 2048 + 2048
    assert 2 * dense + 6 * expert + ends == 3_158_905_600
    assert n_params(window_moe_shapes(cfg)) == 3_158_905_600
    mcfg = drv.model_config(cfg)
    assert [mcfg.kind(i) for i in range(8)] == (["window"] * 3 + ["full"]) * 2
    assert (mcfg.num_experts, mcfg.experts_held) == (128, (0, 64))
    # the spec of the served class, with no weights built
    spec = AfmoeForCausalLM.cache_spec(type("M", (), {"cfg": mcfg})())
    assert spec.slot_bytes(16)["window"] == 25_362_432
    assert spec.block_bytes(16) == 65_536


# --------------------------------------------------------- (f) the counters
@pytest.mark.parametrize("n,new", [(37, 30), (5, 40), (24, 2), (1, 25),
                                   (23, 3), (50, 4), (60, 9)])
def test_the_attention_counter_step_for_step(built, n, new):
    """One request alone: the context counters that the benchmark's readers
    take attention's work from are the closed form — the prompt's tokens,
    then decode rows each at its context, capped at the window in
    ``serving_decode_ctx_window`` (one layer's count; the readers multiply
    by the layers of each kind) — and the expert pairs are 4 a real row of
    an expert layer, held or absent."""
    model, _ = built
    srv = _server(model, telemetry=True)
    srv.submit(_tokens(n, 22), max_new_tokens=new)
    srv.run()
    srv.request_metrics()
    # a token at position p attends p + 1 positions; the first generated
    # token comes from the prompt's last, so the decode rows sit at n ..
    # n + new - 2
    ctx = list(range(n + 1, n + new))
    assert _count(srv, "serving_prefill_ctx") == n * (n + 1) // 2
    assert _count(srv, "serving_decode_ctx") == sum(ctx)
    assert _count(srv, "serving_decode_ctx_window") == \
        sum(min(c, TINY["sliding_window"]) for c in ctx)
    rows = n + new - 1
    held = _count(srv, "serving_moe_pairs", held="1")
    absent = _count(srv, "serving_moe_pairs", held="0")
    assert held + absent == rows * 4 * 6 and 0 < held < rows * 4 * 6


@pytest.mark.parametrize("n,new", [(37, 30), (5, 40), (60, 9)])
def test_the_ring_group_counter_step_for_step(built, n, new):
    """``serving_ring_groups`` counts the groups of one window layer's ring
    walk in each decode row-tick by the copy the kernel makes: at the
    preset's 1 KB head-major blocks (a ring of 4 blocks of 8, walked 4 a
    group) a whole group that starts at the ring's first entry is one copy,
    every other group block by block; nothing is counted with telemetry
    off."""
    from tests.test_selective_scan import _ring_walk_groups_by_hand

    model, _ = built
    srv = _server(model, telemetry=True)
    srv.submit(_tokens(n, 22), max_new_tokens=new)
    srv.run()
    srv.request_metrics()
    # the decode rows' kernel positions: the token written, n .. n+new-2
    groups, fits = _ring_walk_groups_by_hand(
        range(n, n + new - 1), TINY["sliding_window"], 4, BS, 4)
    assert 0 < fits < groups
    assert _count(srv, "serving_ring_groups", copy="group") == fits
    assert _count(srv, "serving_ring_groups", copy="block") == groups - fits
    quiet = _server(model)
    quiet.submit(_tokens(n, 22), max_new_tokens=new)
    quiet.run()
    assert quiet.telemetry.registry.counter(
        "serving_ring_groups").total() == 0
