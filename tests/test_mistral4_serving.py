"""Mistral-Small-4's language model (latent attention, one chip's share of a
many-expert layer) through the serving path, at a tiny preset, against the
benchmark's plain reference (``benchmarks/reference/latent_moe_lm.py``:
float32, expanded attention, a loop over experts, no cache, nothing imported
from ``paddle_tpu``; it routes for itself).

The preset: hidden 64, 4 heads, ranks 32 / 16, nope 8 / rope 8 / v 16, 3
layers, a router over 8 experts of which the top 2, experts 2..5 held here,
width 32, one shared expert; YaRN with an original length of 16, so that the
frequency ramp and a query scale ``a_t`` other than 1 are reached within the
test sequences. Weights are the benchmark's seeded draws at
``initializer_range`` 0.3 (at 0.02 a 64-wide network's logits are all but
flat and any arithmetic would pass).

Tolerances. In float32 program and reference differ by summation order and by
the absorbed form's extra product (``W_uk^T q`` first, then the latent): logits
agree to ``TOL`` = 5e-4 absolute (observed ~5e-5; logits are O(10)). What
that catches is held by the controls: the reference in bfloat16 is off by
> 30 x TOL, a top-1 router, a dropped shared expert, an unrotated cached rope
key and ``a_t`` = 1 by far more. In bfloat16 the program's own rounding is
the bfloat16 control's size (logits of O(10) carry ~3 digits), so there the
bound is that control's, ``TOL_BF16`` = 0.5, which every planted fault above
still exceeds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.cache_spec import CacheSpecError
from paddle_tpu.inference.executor import PagedExecutor
from paddle_tpu.ops import select
from paddle_tpu.telemetry import ENGINE_RID

from benchmarks.drivers import serve_latent_moe as drv
from benchmarks.reference import latent_moe_lm as ref


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


TOL, TOL_BF16 = 5e-4, 0.5
TINY = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "experts_held": [2, 6], "published": {"n_routed_experts": 8},
    "num_experts_per_tok": 2, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "torch_dtype": "float32", "initializer_range": 0.3,
    "rope_parameters": {
        "rope_theta": 10000.0, "factor": 4.0, "rope_type": "yarn",
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "llama_4_scaling_beta": 0.1},
}
BS, CHUNK, L, K = 8, 16, 3, 2


@pytest.fixture(scope="module")
def built():
    return drv.build_model(TINY, seed=11)


def _server(model, alone=False, **kw):
    """``alone``: the same server made to run every chunk in a call of its
    own, by answering the executor's one question for it."""
    kw = {"max_batch": 3, "max_len": 192, "block_size": BS,
          "prefill_chunk": CHUNK, **kw}
    if not alone:
        return GenerationServer(model, cache="paged", **kw)
    asked = PagedExecutor._why_chunks_run_alone
    PagedExecutor._why_chunks_run_alone = lambda self: "asked_to"
    try:
        return GenerationServer(model, cache="paged", **kw)
    finally:
        PagedExecutor._why_chunks_run_alone = asked


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).tolist()


def _count(srv, name, **where):
    c = srv.telemetry.registry.counter(name)
    return int(c.total(where=where) if where else c.total())


def _teacher_forced_logits(model, seq, n_prefill, joint, slot=1):
    """Logits of the SERVING programs at positions ``n_prefill-1 ..
    len(seq)-2``: chunked prefill of ``seq[:n_prefill]`` then one paged
    decode step per further token, fed the known token. ``joint``: the
    chunks run in the decode program's call (every decode row masked), as a
    server of this class dispatches them; else in the chunk program."""
    srv = _server(model)
    ex, params = srv._exec, srv.params
    B = srv.max_batch
    table = np.zeros((srv._table_width,), np.int32)
    need = -(-len(seq) // BS)
    table[:need] = [srv.alloc.alloc() for _ in range(need)]
    flat = list(ex.pools)
    zeros = (jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.float32))
    key = jax.random.PRNGKey(0)
    for start in range(0, n_prefill, CHUNK):
        end = min(start + CHUNK, n_prefill)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :end - start] = seq[start:end]
        ops = (jnp.asarray(chunk), jnp.asarray(table), jnp.int32(start),
               jnp.int32(end - start - 1))
        if joint:
            _, lg, flat, _, stats = ex._decode_chunk_fn(
                params, jnp.zeros((B,), jnp.int32), flat,
                jnp.zeros((B, srv._table_width), jnp.int32),
                jnp.zeros((B,), jnp.int32), *zeros,
                jnp.zeros((B,), jnp.int32), key, ex.prev_stack(None, 1),
                *ops, greedy=True)
        else:
            lg, flat, _, stats = ex._chunk_prefill_fn(
                params, ops[0], flat, *ops[1:])
        # the masked decode rows and the chunk's padding route nowhere
        assert int(stats.sum()) == (end - start) * K * L
    out = [np.asarray(lg[0])]
    bt = np.zeros((B, srv._table_width), np.int32)
    bt[slot] = table
    active = np.zeros((B,), np.int32)
    active[slot] = 1
    for p in range(n_prefill, len(seq) - 1):
        toks = np.zeros((B,), np.int32)
        toks[slot] = seq[p]
        pos = np.zeros((B,), np.int32)
        pos[slot] = p
        stack, flat, _, stats = ex._decode_paged_fn(
            params, jnp.asarray(toks), flat, jnp.asarray(bt),
            jnp.asarray(pos), *zeros, jnp.asarray(active), key,
            greedy=True, ticks=1)
        assert int(stats.sum()) == K * L
        # the logits themselves: the step again, without the sampling
        from paddle_tpu.jit import functional_call

        def call():
            h, _ = model.model.paged_decode_step(
                Tensor(jnp.asarray(toks)[:, None]), ex._pool_views(flat),
                jnp.asarray(bt), jnp.asarray(pos))
            return srv._head(h)

        lg = functional_call(model, params, call_fn=call)
        out.append(np.asarray(lg.value[slot, 0]))
    return np.stack(out)


# ------------------------------------------------ (a) served = the reference
def test_cache_free_forward_matches_the_reference(built):
    model, weights = built
    seq = _tokens(70, 1)
    got = np.asarray(model(Tensor(jnp.asarray([seq]))).value[0])
    want = ref.logits_at(weights, TINY, seq, list(range(len(seq))))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("joint", [False, True],
                         ids=["chunk-program", "chunk-in-the-decode-call"])
@pytest.mark.parametrize("n_prefill", [
    pytest.param(41, id="prompt-ends-inside-a-chunk"),
    pytest.param(32, id="prompt-ends-on-a-chunk-boundary"),
    pytest.param(5, id="prompt-shorter-than-a-block"),
])
def test_chunked_prefill_then_paged_decode_logits_match_the_reference(
        built, n_prefill, joint):
    """Positions run past 2 x the YaRN original length (16), so the ramped
    frequencies and two steps of the query scale are in the comparison."""
    model, weights = built
    seq = _tokens(n_prefill + 24, 2)
    got = _teacher_forced_logits(model, seq, n_prefill, joint)
    pos = list(range(n_prefill - 1, len(seq) - 1))
    want = ref.logits_at(weights, TINY, seq, pos)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_bfloat16_serving_stays_within_the_bfloat16_control():
    """In bfloat16 a near-tied router score can choose another expert than
    the float32 reference does, and that position is then off by an expert's
    whole part (observed over two draws of the weights: 1 and 4 positions
    in 23 off by 2.7 - 5.8, the others by 0.1 - 0.8, which is what the
    reference's own bfloat16 arithmetic reads). So the bound is on the
    median over positions and on the share of positions within twice the
    bound, not on the widest position."""
    cfg = dict(TINY, torch_dtype="bfloat16")
    model, weights = drv.build_model(cfg, seed=12)
    seq = _tokens(60, 3)
    got = _teacher_forced_logits(model, seq, 37, joint=True)
    pos = list(range(36, len(seq) - 1))
    want = ref.logits_at(weights, cfg, seq, pos)
    off = np.abs(got.astype(np.float32) - want).max(-1)
    assert np.median(off) < TOL_BF16
    assert (off < 2 * TOL_BF16).mean() >= 0.75


@pytest.mark.parametrize("mode", ["bf16", "top3", "no_shared", "k_unrotated",
                                  "no_qscale", "drop_1.25"])
def test_the_tolerance_catches(built, mode):
    """What a lower precision or a planted fault moves the reference's own
    logits by: bfloat16 arithmetic lies far past the float32 tolerance, and
    every planted fault (``top3`` is top-1 here) past the bfloat16 one — in
    the median over positions, the statistic that test uses."""
    _, weights = built
    seq = _tokens(80, 4)
    pos = list(range(20, len(seq)))
    want = ref.logits_at(weights, TINY, seq, pos)
    off = np.abs(ref.logits_at(weights, TINY, seq, pos, mode=mode)
                 - want).max(-1)
    if mode in ("bf16", "drop_1.25"):
        # (80 tokens over 8 experts overflow a 1.25 bucket at few positions)
        assert off.max() > 30 * TOL
    else:
        assert np.median(off) > TOL_BF16, np.median(off)


# --------------------------------------------------- (b) absorbed = expanded
def test_absorbed_attention_is_the_expanded_attention(built):
    model, _ = built
    x = Tensor(jnp.asarray([_tokens(50, 5)]))
    a = np.asarray(model(x, absorbed=True).value)
    e = np.asarray(model(x, absorbed=False).value)
    assert np.abs(a - e).max() < TOL
    attn = model.model.layers[0].self_attn
    h = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    np.testing.assert_allclose(np.asarray(attn.dense(h, True)),
                               np.asarray(attn.dense(h, False)),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ (c) the shares add up
def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Router over 8, top 2; four chips hold experts 0-1, 2-3, 4-5, 6-7.
    The routed parts of the four shares plus the shared expert counted once
    = the uncut reference's expert layer, for the program's layer and for
    the reference's own share alike."""
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsLayer
    from paddle_tpu.models.mistral4 import Mistral4DecoderLayer
    from paddle_tpu.models.mistral4 import mistral4_tiny_config

    whole = dict(TINY, n_routed_experts=8, experts_held=[0, 8],
                 published={"n_routed_experts": 8})
    z = ref.sizes(whole)
    rng = np.random.default_rng(6)
    w = {k: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for k, s in {"router": (64, 8), "router_bias": (8,),
                      "w_gate_e": (8, 64, 32), "w_up_e": (8, 64, 32),
                      "w_down_e": (8, 32, 64), "ws_gate": (64, 32),
                      "ws_up": (64, 32), "ws_down": (32, 64)}.items()}
    h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    want = np.asarray(ref.expert_part(h, w, z))
    # what every chip computes alike, counted once
    shared = np.asarray(ref._swiglu(h, w["ws_gate"], w["ws_up"],
                                    w["ws_down"], "f32"))
    ref_sum, got_sum = shared.copy(), shared.copy()
    pairs = 0
    for lo in range(0, 8, 2):
        mine = dict(w, **{k: w[k][lo:lo + 2]
                          for k in ("w_gate_e", "w_up_e", "w_down_e")})
        ref_sum += np.asarray(ref.expert_part(h, mine, z, held=(lo, lo + 2),
                                              with_shared=False))
        layer = Mistral4DecoderLayer(mistral4_tiny_config(
            experts_held=(lo, lo + 2))).mlp
        assert isinstance(layer, HeldExpertsLayer)
        layer.router.weight.set_value(w["router"])
        layer.router_bias.set_value(w["router_bias"])
        layer.experts_gate.set_value(w["w_gate_e"][lo:lo + 2])
        layer.experts_up.set_value(w["w_up_e"][lo:lo + 2])
        layer.experts_down.set_value(w["w_down_e"][lo:lo + 2])
        part, counts = layer.routed(h)
        got_sum = got_sum + np.asarray(part)
        assert int(counts.sum()) == 24 * 2          # held here + elsewhere
        pairs += int(counts[:2].sum())
    assert pairs == 24 * 2                           # every pair held once
    assert np.abs(ref_sum - want).max() < 1e-4
    assert np.abs(got_sum - want).max() < 1e-4


# --------------------------------------------------------- (d) no token drops
@pytest.mark.parametrize("rows", [5, 64])
def test_no_token_is_dropped_when_one_expert_gets_every_row(rows):
    """A router skewed so that experts 3 and 1 win every row: expert 3,
    held here, gets ALL rows (25 x a capacity-1.25 bucket of the even
    share at 64 rows) and computes all of them."""
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsLayer

    layer = HeldExpertsLayer(16, 8, 8, 2, experts_held=(2, 6))
    assert layer.capacity_factor is None
    bias = np.full((8,), -5.0, np.float32)
    bias[3], bias[1] = 5.0, 4.0
    layer.router_bias.set_value(jnp.asarray(bias))
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.standard_normal((rows, 16)), jnp.float32)
    y, counts = layer.routed(h)
    assert counts.tolist() == [0, rows, 0, 0, rows]
    s = jax.nn.sigmoid(h @ layer.router.weight.value)
    w3 = s[:, 3] / (s[:, 3] + s[:, 1])
    e3 = (jax.nn.silu(h @ layer.experts_gate.value[1])
          * (h @ layer.experts_up.value[1])) @ layer.experts_down.value[1]
    np.testing.assert_allclose(np.asarray(y), np.asarray(w3[:, None] * e3),
                               rtol=1e-4, atol=1e-6)
    # rows marked as padding are no one's: not computed, not counted
    valid = jnp.arange(rows) < rows - 2
    y2, c2 = layer.routed(h, valid)
    assert c2.tolist() == [0, rows - 2, 0, 0, rows - 2]
    assert not np.asarray(y2[rows - 2:]).any()
    np.testing.assert_array_equal(np.asarray(y2[:rows - 2]),
                                  np.asarray(y[:rows - 2]))


# ------------------------------- (e) riding = alone; preemption; save/restore
ARRIVALS = [(0, _tokens(9, 1), 30), (2, _tokens(40, 2), 20),
            (3, _tokens(21, 3), 24), (3, _tokens(50, 4), 10),
            (9, _tokens(70, 5), 12)]


def _drive(srv, arrivals):
    rid_of, out, step, remaining = {}, {}, 0, 1
    while remaining or len(rid_of) < len(arrivals):
        for i, (at, prompt, new) in enumerate(arrivals):
            if at == step:
                rid_of[i] = srv.submit(prompt, max_new_tokens=new)
        remaining = srv.step()
        srv.assert_conserved()
        out.update(srv.take_results())
        step += 1
    return {i: out[r] for i, r in rid_of.items()}


def test_chunks_ride_in_the_decode_call_and_emit_what_chunks_alone_emit(
        built):
    model, weights = built
    alone = _server(model, alone=True)
    assert alone._decode_chunk is None
    want = _drive(alone, ARRIVALS)
    srv = _server(model)
    assert srv._exec.chunk_alone_why is None and srv._chunk_prefill is None
    got = _drive(srv, ARRIVALS)
    assert got == want
    assert _count(srv, "serving_prefill_chunks_fused") > 0
    assert _count(srv, "serving_prefill_chunks_alone", reason="moe") == 0
    assert _count(alone, "serving_prefill_chunks_alone",
                  reason="asked_to") == _count(srv, "serving_prefill_chunks")
    for i, (_, p, _) in enumerate(ARRIVALS):
        assert ref.served_gaps(weights, TINY, p, got[i][len(p):])[0].max() \
            < TOL
    # (g) the counters partition: every (row, choice) of every layer is
    # either held here or lives elsewhere, in both servers
    for s in (srv, alone):
        rows = _count(s, "serving_decode_rows") \
            + _count(s, "serving_prefill_tokens")
        held = _count(s, "serving_moe_pairs", held="1")
        assert held + _count(s, "serving_moe_pairs", held="0") == rows * K * L
        assert 0 < held < rows * K * L
        assert _count(s, "serving_moe_load_max") \
            <= held <= 4 * _count(s, "serving_moe_load_max")
        assert _count(s, "serving_moe_experts_active") \
            <= 4 * L * (_count(s, "serving_prefill_chunks")
                        + s._trip_no)
    assert _count(srv, "serving_decode_ctx") == \
        _count(alone, "serving_decode_ctx") > 0


def test_a_capacity_bucket_expert_layer_keeps_the_reason_moe():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64,
                      moe_num_experts=4, moe_top_k=2, dtype="float32",
                      use_flash_attention=False)
    srv = GenerationServer(LlamaForCausalLM(cfg), cache="paged", max_batch=2,
                           max_len=64, block_size=8, prefill_chunk=16)
    assert srv._exec.chunk_alone_why == "moe" and srv._decode_chunk is None


def test_preemption_and_readmission_carry_the_latent_rows(built):
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
    assert srv._slots[0] is None and srv.alloc.blocks_in_use == 0
    other = srv.submit(_tokens(20, 7), max_new_tokens=30)   # takes slot 0
    out = srv.run()
    assert out[rid] == want
    assert ref.served_gaps(weights, TINY, prompt,
                           out[rid][len(prompt):])[0].max() < TOL
    assert len(out[other]) == 50
    names = {s["name"] for s in srv.telemetry.tracer.spans()
             if s["rid"] == rid}
    assert {"swap_out", "swap_in"} <= names
    assert srv.assert_conserved()


def test_save_slot_and_restore_slot_round_trip_a_latent_slot(built):
    """A latent slot owns blocks of the shared pool and nothing beside them:
    ``save_slot`` retires the pending trip and returns no arrays, and
    ``restore_slot`` of that is a no-op — the rows travel with the blocks
    (the test above); a snapshot carries one pool tensor a layer."""
    model, _ = built
    prompt = _tokens(40, 8)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=40)
    want = calm.run()[r0]
    srv = _server(model)
    rid = srv.submit(prompt, max_new_tokens=40)
    for _ in range(12):
        srv.step()
    assert srv._trips
    saved = srv._exec.save_slot(0)
    assert saved == [] and srv._trips == []
    srv._exec.restore_slot(0, saved)
    snap = srv.evacuate()
    kv = snap["requests"][0]["kv"]
    assert kv["n_extra"] == 0
    assert len(kv["arrays"]) == L
    fresh = _server(model)
    fresh.restore(snap)
    assert fresh.run()[rid] == want


def test_prefix_blocks_are_shared_between_requests(built):
    model, weights = built
    srv = _server(model)
    shared = _tokens(48, 9)
    outs = []
    for tail in (1, 2):
        rid = srv.submit(shared + [tail], max_new_tokens=6)
        outs.append((shared + [tail], srv.run()[rid]))
    assert srv.kv_stats()["prefix_hit_blocks"] == 48 // BS
    for p, seq in outs:
        assert ref.served_gaps(weights, TINY, p, seq[len(p):])[0].max() < TOL


# ----------------------------------------------------- (f) bytes, closed form
def test_cache_bytes_equal_the_closed_form(built):
    """A latent row is 16 + 8 = 24 values wide and held in whole 128-lane
    tiles: 128 values x 4 B a token a layer, in ONE pool a layer."""
    model, _ = built
    srv = _server(model, max_batch=2, num_blocks=40)
    spec = srv.cache_spec
    assert [l.kind for l in spec.layers] == ["latent"] * L
    assert spec.layers[0].head_dim == 24 and spec.layers[0].row_width == 128
    per_block = L * BS * 128 * 4
    assert spec.block_bytes(BS) == spec.latent_block_bytes(BS) == per_block
    ex = srv._exec
    assert len(ex.pools) == L and ex.slot_pools == []
    assert all(p.shape == (40, BS, 128) for p in ex.pools)
    assert sum(p.nbytes for p in ex.pools) == 40 * per_block
    b = srv.cache_bytes()
    assert b["cache_bytes_latent_allotted"] == 39 * per_block
    assert b["cache_bytes_full_allotted"] == b["cache_bytes_full"] == 0
    srv.submit(_tokens(10, 10), max_new_tokens=60)
    seen = set()
    while srv.step():
        if srv._slots[0] is None or srv._prefilling[0]:
            continue
        b = srv.cache_bytes()
        assert b["cache_bytes_latent"] == \
            len(srv._slots[0].table) * per_block
        assert b["cache_bytes_latent"] in (
            -(-int(srv.pos[0]) // BS) * per_block,
            -(-(int(srv.pos[0]) + 1) // BS) * per_block)
        seen.add(len(srv._slots[0].table))
    assert max(seen) >= 8
    assert srv.kv_stats()["cache_bytes_latent"] == 0


# ------------------------------------------------- refusals, and tick's order
def test_unsupported_features_are_refused_by_name_at_construction(built):
    model, _ = built
    from paddle_tpu.inference.speculative import SpecConfig

    for kw, what in (({"kv_quant": "int8"}, "kv_quant='int8'"),
                     ({"spec": SpecConfig(k=2)}, "spec="),
                     ({"lora": object()}, "lora="),
                     ({"mesh": "tp=2"}, "mesh=")):
        with pytest.raises(CacheSpecError, match="'latent' cache") as e:
            _server(model, **kw)
        assert what in str(e.value)
    with pytest.raises(CacheSpecError, match="cache='dense'"):
        GenerationServer(model, cache="dense", max_batch=2, max_len=64)


def test_the_expert_counts_add_no_program_call_and_no_phase_to_a_tick(built):
    """The per-layer loads come back with the trip's outputs and are folded
    where the trip is read: a tick's phases and their order are what they
    are for a dense decoder, and the server has its two programs."""
    from paddle_tpu.analysis.recompile_guard import compile_count

    model, _ = built
    srv = _server(model, telemetry=True)
    srv.submit(_tokens(20, 11), max_new_tokens=12)
    srv.submit(_tokens(37, 12), max_new_tokens=12)
    srv.run()                                     # both programs compiled
    n0 = compile_count()
    srv.submit(_tokens(33, 13), max_new_tokens=8)
    srv.step()
    srv.submit(_tokens(18, 14), max_new_tokens=8)
    srv.run()
    assert compile_count() == n0
    # (the engine row: the device-queue row beside it is not a tick's phase)
    spans = srv.telemetry.tracer.spans(ENGINE_RID)
    by_tick = {}
    for s in spans:
        by_tick.setdefault(s.get("args", {}).get("tick"), []).append(
            s["name"])
    seen = {n for names in by_tick.values() for n in names}
    assert seen <= {"tick", "admit", "prefill", "first_token_wait",
                    "decode_dispatch", "decode_wait", "harvest"}
    order = ["admit", "prefill", "decode_dispatch", "decode_wait", "harvest"]
    for names in by_tick.values():
        main = [n for n in names if n in order]
        assert main == sorted(main, key=order.index)
    assert _count(srv, "serving_moe_pairs") == K * L * (
        _count(srv, "serving_decode_rows")
        + _count(srv, "serving_prefill_tokens"))
