"""Granite 4.0-H (Mamba-2 layers, an attention layer without positions, one
chip's share of a many-expert layer in every layer) through the serving path,
at a tiny preset, against the benchmark's plain reference
(``benchmarks/reference/ssm_moe_lm.py``: float32, the sequential recurrence, a
loop over experts, no cache, nothing imported from ``paddle_tpu``; it routes
for itself).

The preset: hidden 64, four layers of which the second attends (4 query / 2
KV heads of 16, score scale 1/8 — not 16^-1/2), Mamba-2 with 8 heads of 16
and a state of 128 in blocks of 16 tokens, a router over 8 experts of which
the top 3, experts 2..5 held here, width 32, a shared MLP of width 48, the
published scalar multipliers (12, 0.22, 16). Weights are the benchmark's
seeded draws at ``initializer_range`` 0.3 (at 0.02 a 64-wide network's logits
are all but flat and any arithmetic would pass), the embedding at 0.3 / 12.

Tolerances. In float32 program and reference differ by summation order and by
the chunked form of the scan (matrix products of decayed terms where the
reference walks the tokens): logits agree to ``TOL`` = 5e-6 absolute
(logits are O(0.01): a tied head over an embedding of width 0.025, divided
by 16). What that catches is held by the controls:
the reference with its SSM state rounded to bfloat16 each token, top 2 for
top 3, a softmax over all 8 router logits, a dropped shared MLP, a residual
multiplier of 1, a score scale of d^-1/2, a rotation and norm-before-gate
are each off by more than 10 x TOL.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.cache_spec import CacheSpecError
from paddle_tpu.jit import functional_call
from paddle_tpu.ops import select
from paddle_tpu.telemetry import ENGINE_RID

from benchmarks.drivers import serve_ssm_moe as drv
from benchmarks.reference import ssm_moe_lm as ref
from tests.test_fused_tick import _drive, _partitioned as _chunks
from tests.test_fused_tick import _server as _fused_tick_server


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # process-wide, and earlier test files may have left it pinned
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


TOL = 5e-6
TINY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.125, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 128,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "position_embedding_type": "nope", "num_local_experts": 4,
    "experts_held": [2, 6], "published": {"num_local_experts": 8},
    "num_experts_per_tok": 3, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4096, "tie_word_embeddings": True,
    "torch_dtype": "float32", "initializer_range": 0.3,
}
BS, CHUNK = 8, 16
MAMBA = [0, 2, 3]


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


def _teacher_forced_logits(model, seq, n_prefill, slot=1, start_state=None):
    """Logits of the SERVING programs at positions ``n_prefill-1 ..
    len(seq)-2``: chunked prefill of ``seq[:n_prefill]`` (logits of its last
    token), then one paged decode step per further token of ``seq``, fed the
    known token — every program the server dispatches, minus its sampling.
    ``start_state``: slot pools to start from (another request's leftovers).
    Returns (logits, the slot pools at the end)."""
    srv = _server(model)
    ex, params = srv._exec, srv.params
    table = np.zeros((srv._table_width,), np.int32)
    need = -(-len(seq) // BS)
    table[:need] = [srv.alloc.alloc() for _ in range(need)]
    flat = list(ex.pools)
    slot_p = list(ex.slot_pools if start_state is None else start_state)
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
    return np.stack(out), slot_p


# ------------------------------------------------------- (a) against the reference
def test_cache_free_forward_matches_the_reference(built):
    model, weights = built
    seq = _tokens(70, 1)
    got = np.asarray(model(Tensor(jnp.asarray([seq]))).value[0])
    want = ref.logits_at(weights, TINY, seq, list(range(len(seq))))
    assert np.abs(want).max() > 0.03         # 6,000 x TOL: not flat
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n_prefill", [
    pytest.param(41, id="ragged-last-chunk-and-a-block-boundary-inside"),
    pytest.param(32, id="prompt-ends-on-a-chunk-boundary"),
    pytest.param(5, id="prompt-shorter-than-a-block"),
])
def test_chunked_prefill_then_paged_decode_logits_match_the_reference(
        built, n_prefill):
    """Prefill in chunks of 16 (= one block of the chunked scan; the state
    handed from chunk to chunk and from the last chunk to the decode rows),
    then decode through the cache, against the reference's full forward."""
    model, weights = built
    seq = _tokens(n_prefill + 40, 2)
    got, _ = _teacher_forced_logits(model, seq, n_prefill)
    pos = list(range(n_prefill - 1, len(seq) - 1))
    want = ref.logits_at(weights, TINY, seq, pos)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_a_chunk_of_several_scan_blocks_matches_the_reference(built,
                                                              monkeypatch):
    """A prefill chunk of 32 = two blocks of the chunked scan (the block
    boundary inside a chunk) and four calls of the prefill attention (its
    queries go in blocks of rows)."""
    from paddle_tpu.models import granitemoehybrid

    monkeypatch.setattr(granitemoehybrid, "_ATTN_ROWS", 8)
    model, weights = built
    seq = _tokens(90, 3)
    srv = _server(model, prefill_chunk=32)
    rid = srv.submit(seq[:50], max_new_tokens=30)
    out = srv.run()[rid]
    gaps = ref.served_gaps(weights, TINY, seq[:50], out[50:])[0]
    assert gaps.max() < TOL


def test_a_slot_reused_after_another_request_starts_from_zero(built):
    """The first chunk of a request zeroes the slot's state: what another
    request left there does not reach it."""
    model, weights = built
    _, left = _teacher_forced_logits(model, _tokens(60, 4), 37)
    assert all(float(jnp.abs(p[1]).max()) > 0 for p in left)
    seq = _tokens(50, 5)
    got, _ = _teacher_forced_logits(model, seq, 21, start_state=left)
    want = ref.logits_at(weights, TINY, seq, list(range(20, len(seq) - 1)))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("mode", ["state_bf16", "top9", "softmax_all",
                                  "no_shared", "res_1", "scale_sqrt", "rope",
                                  "norm_then_gate"])
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
    got, _ = _teacher_forced_logits(model, seq, 37)
    pos = list(range(36, len(seq) - 1))
    want = ref.logits_at(weights, cfg, seq, pos)
    control = ref.logits_at(weights, cfg, seq, pos, mode="bf16")
    assert np.abs(got - want).max() < 3 * np.abs(control - want).max()


# ------------------------------------------------------------- (b) the shares
def test_the_two_shares_and_the_shared_mlp_once_are_the_uncut_block():
    """Router over 8, top 3; two chips hold experts 0-3 and 4-7. The routed
    parts of the two shares plus the shared MLP counted once = the uncut
    reference's expert block, for the program's layer and for the
    reference's own share alike."""
    from paddle_tpu.models.granitemoehybrid import (
        GraniteMoeHybridLayer, granitemoehybrid_tiny_config)

    whole = dict(TINY, num_local_experts=8, experts_held=[0, 8])
    z = ref.sizes(whole)
    rng = np.random.default_rng(8)
    w = {k: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for k, s in {"router": (64, 8), "w_gate_e": (8, 64, 32),
                      "w_up_e": (8, 64, 32), "w_down_e": (8, 32, 64),
                      "ws_gate": (64, 48), "ws_up": (64, 48),
                      "ws_down": (48, 64)}.items()}
    h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    want = np.asarray(ref.expert_part(h, w, z))
    # what every chip computes alike, counted once
    shared = np.asarray(ref._swiglu(h, w["ws_gate"], w["ws_up"],
                                    w["ws_down"], "f32"))
    ref_sum, got_sum, pairs = shared.copy(), shared.copy(), 0
    for lo in (0, 4):
        mine = dict(w, **{k: w[k][lo:lo + 4]
                          for k in ("w_gate_e", "w_up_e", "w_down_e")})
        ref_sum += np.asarray(ref.expert_part(h, mine, z, held=(lo, lo + 4),
                                              with_shared=False))
        layer = GraniteMoeHybridLayer(granitemoehybrid_tiny_config(
            experts_held=(lo, lo + 4)), "mamba").mlp
        layer.router.weight.set_value(w["router"])
        layer.experts_gate.set_value(w["w_gate_e"][lo:lo + 4])
        layer.experts_up.set_value(w["w_up_e"][lo:lo + 4])
        layer.experts_down.set_value(w["w_down_e"][lo:lo + 4])
        part, counts = layer.routed(h)
        got_sum = got_sum + np.asarray(part)
        assert int(counts.sum()) == 24 * 3          # held here + elsewhere
        pairs += int(counts[:4].sum())
    assert pairs == 24 * 3                           # every pair held once
    assert np.abs(ref_sum - want).max() < 1e-4
    assert np.abs(got_sum - want).max() < 1e-4


def test_the_routing_rule_is_an_argument_of_the_layer():
    from paddle_tpu.incubate.distributed.models.moe import HeldExpertsLayer
    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        deepseek_v3_rule, softmax_of_chosen)

    logits = jnp.asarray(np.random.default_rng(9).standard_normal((6, 8)),
                         jnp.float32)
    idx, w = softmax_of_chosen(logits, 3)
    top = np.sort(np.asarray(logits), -1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.asarray(w),
                               np.exp(top) / np.exp(top).sum(-1)[:, None],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    assert (np.take_along_axis(np.asarray(logits), np.asarray(idx), -1)
            == top).all()
    with pytest.raises(ValueError, match="no selection bias"):
        softmax_of_chosen(logits, 3, jnp.zeros((8,)))
    bias = jnp.asarray([9., 0, 0, 0, 0, 0, 0, 8.])
    idx, w = deepseek_v3_rule(True, 2.0)(logits, 2, bias)
    assert np.asarray(idx).tolist() == [[0, 7]] * 6
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.0, rtol=1e-6)
    # the default stays DeepSeek-V3's, with the bias it selects by; a layer
    # given another rule holds none
    assert HeldExpertsLayer(16, 8, 8, 2).router_bias is not None
    plain = HeldExpertsLayer(16, 8, 8, 2, rule=softmax_of_chosen,
                             selection_bias=False)
    assert plain.router_bias is None
    assert "router_bias" not in dict(plain.named_parameters())


# ------------------------------------------- (c) the state travels with a request
def _gaps(weights, prompt, served):
    return ref.served_gaps(weights, TINY, prompt, served)[0]


def test_two_requests_of_very_different_lengths_in_one_batch(built):
    model, weights = built
    prompts = [_tokens(90, 14), _tokens(3, 15)]
    srv = _server(model)
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (60, 45))]
    out = srv.run()
    for p, r, n in zip(prompts, rids, (60, 45)):
        assert out[r][:len(p)] == p and len(out[r]) == len(p) + n
        assert _gaps(weights, p, out[r][len(p):]).max() < TOL
    assert srv.assert_conserved()


def test_preemption_and_readmission_carry_the_state(built):
    """A decoding request is swapped out (its blocks and its Mamba-2 state
    go to the host), another request takes its slot, and it resumes: same
    tokens as the undisturbed run, and still the reference's."""
    model, weights = built
    prompt = _tokens(50, 16)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=50)
    want = calm.run()[r0]

    srv = _server(model, telemetry=True)
    rid = srv.submit(prompt, max_new_tokens=50)
    while len(srv._slots[0].generated if srv._slots[0] else []) < 12:
        srv.step()
    assert srv._preempt_slot(0)
    assert _count(srv, "serving_state_saves") == 1
    # zero what the slot held: a resume that read stale device state would
    # pass by accident
    srv._exec.restore_slot(0, [np.zeros_like(a)
                               for a in srv._exec.save_slot(0)])
    other = srv.submit(_tokens(20, 17), max_new_tokens=30)   # takes slot 0
    out = srv.run()
    assert out[rid] == want
    assert _gaps(weights, prompt, out[rid][len(prompt):]).max() < TOL
    assert len(out[other]) == 50
    names = {s["name"] for s in srv.telemetry.tracer.spans()
             if s["rid"] == rid}
    assert {"state_save", "state_restore", "swap_out", "swap_in"} <= names
    assert srv.assert_conserved()


def test_save_slot_is_the_reference_state_and_restore_round_trips(built):
    """``save_slot`` hands out, per Mamba-2 layer, the float32 state the
    reference reaches over the tokens consumed (what the benchmark's check
    reads), and the conv tail; ``restore_slot`` puts them back bit for
    bit."""
    model, weights = built
    prompt = _tokens(45, 18)
    srv = _server(model)
    srv.submit(prompt, max_new_tokens=30)
    for _ in range(14):
        srv.step()
    saved = srv._exec.save_slot(0)
    req = srv._slots[0]
    consumed = (list(req.prompt) + list(req.generated))[:int(srv.pos[0])]
    want = ref.states_at(weights, TINY, consumed)
    spec = srv.cache_spec
    assert [l.kind for l in spec.layers] == ["state", "full", "state",
                                             "state"]
    for j, i in enumerate(MAMBA):
        ssm, conv = saved[2 * j], saved[2 * j + 1]
        assert ssm.dtype == np.float32 and ssm.shape == (8, 16, 128)
        assert conv.shape == (3, 8 * 16 + 256)
        assert np.abs(ssm - want[i]).max() < 1e-4 * np.abs(want[i]).max()
    srv._exec.restore_slot(2, saved)
    for a, b in zip(saved, srv._exec.save_slot(2)):
        np.testing.assert_array_equal(a, b)


def test_snapshot_and_restore_carry_the_state(built):
    model, _ = built
    prompt = _tokens(40, 19)
    calm = _server(model)
    r0 = calm.submit(prompt, max_new_tokens=40)
    want = calm.run()[r0]
    srv = _server(model)
    rid = srv.submit(prompt, max_new_tokens=40)
    for _ in range(12):
        srv.step()
    snap = srv.evacuate()
    assert snap["requests"][0]["kv"]["n_extra"] == len(srv._exec.slot_pools)
    fresh = _server(model)
    fresh.restore(snap)
    assert fresh.run()[rid] == want


# ---------------------------- (c') a chunk rides in the decode trip's call
def _two_programs(model, **kw):
    """The same server made to run every chunk through ``_chunk_prefill_fn``
    (the path of a slot-state class WITHOUT the joint step)."""
    return _fused_tick_server(model, two_programs=True, **{
        "max_batch": 3, "max_len": 192, "block_size": BS,
        "prefill_chunk": CHUNK, **kw})


def _joint_operands(srv, seq, slot, rows, chunk_no):
    """A state worth comparing, and the operands of one step over it: the
    slots in ``rows`` hold a request of 20 tokens each and are about to
    decode its next token, ``slot`` has had ``chunk_no`` chunks of ``seq``
    and is about to take the next. Returns (flat pools, slot pools, the
    decode operands (tokens, tables, pos), the chunk operands (chunk,
    table, start, last_idx, slot triple))."""
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
        mine = _tokens(21, 40 + s)
        prefill(s, mine, 20)
        tokens[s], pos[s] = mine[20], 20
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
    ((0, 2), 1), ((0, 2), 0), ((2,), 2), ((), 1)],
    ids=["second_chunk_of_a_prompt", "first_chunk_starts_from_zero",
         "final_partial_chunk", "every_decode_row_masked"])
def test_the_joint_step_is_the_two_steps_one_after_the_other(built, rows,
                                                            chunk_no):
    """``paged_decode_chunk_step`` over B + C rows against
    ``paged_decode_step`` and then ``paged_prefill_chunk`` on the same
    views: the decode rows' hidden states, the chunk's row at ``last_idx``,
    the K/V pool, every slot's state and conv tail. The rows of one product
    are independent, so what differs is summation order at most; a slot
    that neither decodes nor prefills keeps its state bit for bit."""
    model, _ = built
    srv = _server(model)
    ex, params = srv._exec, srv.params
    seq = _tokens(41, 30)                       # 16 + 16 + 9
    flat, slot_p, (tokens, tables, pos), ck = _joint_operands(
        srv, seq, 1, rows, chunk_no)
    chunk, table, start, last_idx, triple = ck
    B = srv.max_batch
    active = np.zeros((B,), np.int32)
    active[list(rows)] = 1
    bt = np.where(active[:, None] > 0, tables, 0)
    masked = jnp.asarray(active), jnp.asarray(bt), jnp.asarray(pos * active)

    def two_steps():
        act, btv, posv = masked
        h_rows, new = model.model.paged_decode_step(
            Tensor(jnp.asarray(tokens)[:, None]),
            ex._pool_views(flat, slot_p), btv, posv, active=act)
        h_last, new = model.model.paged_prefill_chunk(
            Tensor(chunk), new, table, start, last_idx=last_idx, slot=triple)
        model.model.take_step_stats()
        return jnp.concatenate([h_rows.value[:, 0], h_last.value[0]]), new

    def joint():
        act, btv, posv = masked
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
                               atol=1e-6)
    (wf, ws), (gf, gs) = ex._flat_pools(want), ex._flat_pools(got)
    for a, b in zip(gf, wf):
        # (block 0 is the masked rows' scratch: nobody reads it)
        np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:],
                                   rtol=2e-5, atol=1e-6)
    before = [np.asarray(a) for a in slot_p]
    for a, b, was in zip(gs, ws, before):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(float(np.abs(b).max()), 1e-6)
        assert np.abs(a - b).max() <= 2e-5 * scale
        for s in range(B):
            if s != 1 and s not in rows:
                np.testing.assert_array_equal(a[s], was[s])
    # the chunk moved its slot's state, a decoding row its own
    assert any(np.abs(np.asarray(a)[1] - was[1]).max() > 0
               for a, was in zip(gs, before))


MEETS = [(0, _tokens(9, 31), 30), (2, _tokens(40, 32), 20),
         (3, _tokens(21, 33), 24), (9, _tokens(70, 34), 12)]
TOGETHER = [(0, _tokens(12, 35), 40), (3, _tokens(37, 36), 10),
            (3, _tokens(50, 37), 10)]
LONE = [(0, _tokens(37, 38), 6)]


@pytest.mark.parametrize("case", ["a_chunk_meets_decoding_rows",
                                  "two_prefilling_slots_in_one_tick",
                                  "a_lone_prompt"])
def test_chunks_that_ride_serve_the_references_tokens(built, case):
    """Token for token what the two-program path serves, and the
    reference's; how each chunk went is counted under ``fused`` /
    ``second_chunk`` / ``no_decoding_row``, never ``slot_state``."""
    model, weights = built
    arrivals = {"a_chunk_meets_decoding_rows": MEETS,
                "two_prefilling_slots_in_one_tick": TOGETHER,
                "a_lone_prompt": LONE}[case]
    ref_srv = _two_programs(model, max_batch=4)
    assert ref_srv._decode_chunk is None
    want = _drive(ref_srv, arrivals)
    srv = _server(model, max_batch=4)
    got = _drive(srv, arrivals,
                 check=lambda s, _: s.assert_conserved())
    assert got == want
    for i, (_, prompt, new) in enumerate(arrivals):
        assert _gaps(weights, prompt, got[i][len(prompt):]).max() < TOL
    total, fused, alone = _chunks(srv)
    assert _chunks(ref_srv) == (total, 0, {"two_programs": total})
    assert set(alone) <= {"no_decoding_row", "second_chunk"}
    if case == "a_lone_prompt":
        assert (total, fused, alone) == (3, 0, {"no_decoding_row": 3})
    elif case == "two_prefilling_slots_in_one_tick":
        assert alone["second_chunk"] >= 3 and fused >= 3
    else:
        assert fused >= total // 2 and alone["no_decoding_row"] >= 1


def test_a_masked_call_leaves_every_slots_state_bit_for_bit(built):
    """A tick's second chunk runs the joint program with every decode row
    masked: the rows' update is skipped (``lax.cond`` on the program's own
    ``active``), so the slots that decode keep state AND conv tail as they
    were, whatever tokens the masked rows carry."""
    model, _ = built
    srv = _server(model)
    ex = srv._exec
    seq = _tokens(41, 30)
    flat, slot_p, (tokens, tables, pos), ck = _joint_operands(
        srv, seq, 1, (0, 2), 1)
    before = [np.asarray(a) for a in slot_p]
    B = srv.max_batch
    zeros = jnp.zeros((B,), jnp.int32)
    out = ex._decode_chunk_fn(
        srv.params, jnp.asarray(tokens), flat,
        jnp.zeros((B, srv._table_width), jnp.int32), zeros,
        jnp.zeros((B,), jnp.float32), zeros, jnp.zeros((B,), jnp.float32),
        zeros, srv._base_key, ex.prev_stack(None, 1), *ck[:4], True,
        slot_p, ck[4])
    _, lg, _, new_slot, _ = out
    assert np.isfinite(np.asarray(lg)).all()
    for a, was in zip(new_slot, before):
        a = np.asarray(a)
        for s in (0, 2):
            np.testing.assert_array_equal(a[s], was[s])
        assert np.abs(a[1] - was[1]).max() > 0


LONG = [(0, _tokens(21, 41), 40), (0, _tokens(9, 43), 40),
        (4, _tokens(60, 42), 30)]


@pytest.mark.parametrize("victim", ["a_decoding_row", "the_prefilling_slot"])
def test_preempt_with_a_joint_trip_pending_carries_the_state(built, victim):
    model, weights = built
    want = _drive(_two_programs(model), LONG)
    hit = []

    def preempt(srv, step):
        if step == 6:
            assert srv._trips and _chunks(srv)[1] >= 2 and srv._prefilling[2]
            assert srv._slots[2].pf_next == 48          # 3 of 4 chunks ran
            assert srv._preempt_slot(0 if victim == "a_decoding_row" else 2)
            assert srv._trips == []
            hit.append(step)
        srv.assert_conserved()

    srv = _server(model, telemetry=True)
    got = _drive(srv, LONG, check=preempt)
    assert got == want and hit == [6]
    assert _count(srv, "serving_decode_trips_retired_early",
                  reason="preempt") == 1
    if victim == "a_decoding_row":
        assert _count(srv, "serving_state_saves") == 1
    for i, (_, prompt, new) in enumerate(LONG):
        assert _gaps(weights, prompt, got[i][len(prompt):]).max() < TOL
    _chunks(srv)


def test_save_slot_with_a_joint_trip_pending_is_the_reference_state(built):
    """``save_slot`` retires the pending joint trip first: a decoding
    slot's state is the reference's over exactly the tokens the engine then
    holds, and the prefilling slot's over the chunks that ran."""
    model, weights = built
    srv = _server(model, telemetry=True)
    for step in range(7):
        for at, prompt, new in LONG:
            if at == step:
                srv.submit(prompt, max_new_tokens=new)
        srv.step()
    assert srv._trips and srv._prefilling[2] and _chunks(srv)[1] >= 2
    saved = {s: srv._exec.save_slot(s) for s in (0, 2)}
    assert srv._trips == []
    consumed = {}
    req = srv._slots[0]
    consumed[0] = (list(req.prompt) + list(req.generated))[:int(srv.pos[0])]
    consumed[2] = list(srv._slots[2].prompt)[:srv._slots[2].pf_next]
    assert len(consumed[2]) == 48
    for s, toks in consumed.items():
        want = ref.states_at(weights, TINY, toks)
        for j, i in enumerate(MAMBA):
            ssm = saved[s][2 * j]
            assert np.abs(ssm - want[i]).max() < 1e-4 * np.abs(want[i]).max()


def test_snapshot_and_restore_with_a_joint_trip_pending(built):
    model, _ = built
    want = _drive(_two_programs(model), LONG)
    srv = _server(model, telemetry=True)
    rids = {}
    for step in range(7):
        for i, (at, prompt, new) in enumerate(LONG):
            if at == step:
                rids[i] = srv.submit(prompt, max_new_tokens=new)
        srv.step()
    assert srv._trips and srv._prefilling[2] and _chunks(srv)[1] >= 2
    snap = srv.snapshot()
    assert srv._trips == []
    fresh = _server(model)
    fresh.restore(snap)
    out = fresh.run()
    assert [out[rids[i]] for i in range(3)] == [want[i] for i in range(3)]
    out = srv.run()                    # the captured server goes on
    assert [out[rids[i]] for i in range(3)] == [want[i] for i in range(3)]
    srv.assert_conserved()


def test_the_benchmarks_warm_up_compiles_all_a_window_uses(built):
    """The Granite case of ``tests/test_fused_tick.py``'s warm-up test:
    ``benchmarks/drivers/serve_paged.py::measure`` warms with two prompts of
    ``prefill_chunk + 9`` tokens, 4 new tokens each, drained. After it a
    chunk that meets decoding rows, two chunks in a tick, a decode-only tick
    and a masked chunk compile nothing: the server has its two programs."""
    from paddle_tpu.analysis.recompile_guard import compile_count

    model, _ = built
    srv = _server(model, max_batch=4)
    assert srv._chunk_prefill is None
    rng = np.random.default_rng(0)
    for _ in range(2):
        srv.submit(rng.integers(1, TINY["vocab_size"],
                                size=srv.prefill_chunk + 9).tolist(),
                   max_new_tokens=4, temperature=0.0)
    srv.run()
    warm = _chunks(srv)
    assert warm[0] == 4
    before = compile_count()
    got = _drive(srv, MEETS + TOGETHER)
    assert compile_count() == before
    total, fused, alone = _chunks(srv)
    assert fused - warm[1] >= 8 and alone["second_chunk"] >= 3
    assert len(got) == len(MEETS) + len(TOGETHER)


# ------------------------------------------------------- (d) what is refused
def test_prefix_sharing_is_off_and_unsupported_features_are_named(built):
    model, _ = built
    srv = _server(model)
    # slot state does not keep a chunk out of the decode trip's call: the
    # class has the joint step, so the server has the two programs of a
    # dense one
    assert srv.cache_spec.has_slot_state
    assert srv._exec.chunk_alone_why is None and srv._chunk_prefill is None
    shared = _tokens(48, 20)
    for tail in (1, 2):
        srv.submit(shared + [tail], max_new_tokens=4)
    srv.run()
    st = srv.kv_stats()
    assert st["prefix_hit_blocks"] == 0 and st["blocks_cached"] == 0
    from paddle_tpu.inference.speculative import SpecConfig

    for kw in ({"kv_quant": "int8"}, {"spec": SpecConfig(k=2)},
               {"mesh": "tp=2"}, {"mesh": "cp=2"}):
        with pytest.raises(CacheSpecError, match="per-slot state"):
            _server(model, **kw)
    with pytest.raises(CacheSpecError, match="lora="):
        _server(model, lora=object())
    with pytest.raises(CacheSpecError, match="cache='dense'"):
        GenerationServer(model, cache="dense", max_batch=2, max_len=64)


def test_a_configuration_this_class_cannot_express_is_refused():
    from paddle_tpu.models.granitemoehybrid import (
        GraniteMoeHybridConfig, granitemoehybrid_tiny_config)

    for kw, why in (({"position_embedding_type": "rope"}, "no positions"),
                    ({"mamba_n_groups": 2}, "one group"),
                    ({"tie_word_embeddings": False}, "tied"),
                    ({"mamba_d_head": 8}, "mamba_expand"),
                    ({"layer_types": ("mamba", "window", "mamba", "mamba")},
                     "layer_types")):
        with pytest.raises(ValueError, match=why):
            granitemoehybrid_tiny_config(**kw)
    pub = GraniteMoeHybridConfig()
    assert pub.layer_types.count("attention") == 4
    assert [i for i, k in enumerate(pub.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert (pub.d_inner, pub.conv_dim) == (8192, 8448)


# ------------------------------------------------------------- (e) the bytes
def test_cache_bytes_equal_the_closed_form(built):
    model, _ = built
    srv = _server(model, max_batch=2)
    spec = srv.cache_spec
    state = 3 * (8 * 16 * 128 * 4 + 3 * (8 * 16 + 256) * 4)
    assert spec.slot_bytes(BS) == {"window": 0, "state": state}
    assert state == drv.state_bytes_per_slot(TINY)
    per_block = 2 * BS * 2 * 16 * 4               # one layer, K and V
    assert spec.block_bytes(BS) == per_block
    ex = srv._exec
    assert sum(p.nbytes for p in ex.pools) == srv.alloc.num_blocks * per_block
    assert sum(p.nbytes for p in ex.slot_pools) == state * srv.max_batch
    srv.submit(_tokens(10, 21), max_new_tokens=20)
    for _ in range(8):
        srv.step()
    b = srv.cache_bytes()
    assert b["cache_bytes_state"] == state
    assert b["cache_bytes_full"] == len(srv._slots[0].table) * per_block


def test_the_published_cut_has_the_bytes_the_issue_reckons():
    """The closed forms of the benchmark configuration: parameters of chip
    0's share and the bytes a slot's recurrent state is allotted."""
    import json
    import os

    from benchmarks.weights_ssm_moe import n_params, ssm_moe_shapes

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "granite-4.0-h-small-l10-ep2.json")) as f:
        cfg = json.load(f)
    mamba = (4096 * 16768 + 8192 * 4096 + 4 * 8448 + 8448 + 3 * 128 + 8192)
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    rest = (3 * 4096 * 1536 + 4096 * 72 + 2 * 4096
            + 36 * 3 * 4096 * 768)
    assert (mamba, attn) == (102_286_976, 41_943_040)
    assert mamba + rest == 461_203_072 and attn + rest == 400_859_136
    want = 9 * (mamba + rest) + (attn + rest) + 50_176 * 4096 + 4096
    assert want == 4_757_211_776
    assert n_params(ssm_moe_shapes(cfg)) == want
    assert drv.state_bytes_per_slot(cfg) == 38_204_928
    spec_cfg = drv.model_config(cfg)
    assert spec_cfg.layer_types.count("mamba") == 9
    assert spec_cfg.layer_types[5] == "attention"
    assert spec_cfg.num_local_experts == 72
    assert spec_cfg.experts_held == (0, 36)


# --------------------------------------------------------- (f) the counters
def test_the_counters_step_for_step(built):
    """One request alone: every chunk meets no decoding row and none runs
    under ``slot_state``; the decode rows and contexts, the expert pairs (3
    a real row a layer, held or absent) and the state's gauges are what the closed forms say; the expert
    counts add no program call and no phase to a tick."""
    model, _ = built
    srv = _server(model, telemetry=True)
    n, new = 37, 12
    srv.submit(_tokens(n, 22), max_new_tokens=new)
    from paddle_tpu.analysis.recompile_guard import compile_count

    srv.run()
    srv.request_metrics()
    ticks = new - 1
    n0 = compile_count()
    assert _count(srv, "serving_prefill_chunks") == 3
    assert _count(srv, "serving_prefill_chunks_alone",
                  reason="no_decoding_row") == 3
    assert _count(srv, "serving_prefill_chunks_alone",
                  reason="slot_state") == 0
    assert _count(srv, "serving_prefill_tokens") == n
    assert _count(srv, "serving_decode_rows") == ticks
    assert _count(srv, "serving_decode_ctx") == sum(range(n + 1, n + new))
    rows = n + ticks
    held = _count(srv, "serving_moe_pairs", held="1")
    absent = _count(srv, "serving_moe_pairs", held="0")
    assert held + absent == rows * 3 * 4
    assert 0 < held < rows * 3 * 4
    assert 0 < _count(srv, "serving_moe_experts_active") <= (3 + ticks) * 16
    assert _count(srv, "serving_moe_load_max") >= held / 4
    # (the engine row: the device-queue row beside it is not a tick's phase)
    seen = {s["name"] for s in srv.telemetry.tracer.spans(ENGINE_RID)}
    assert seen <= {"tick", "admit", "prefill", "first_token_wait",
                    "decode_dispatch", "decode_wait", "harvest"}
    # a second request compiles nothing: the server has its two programs
    srv.submit(_tokens(21, 23), max_new_tokens=5)
    srv.run()
    assert compile_count() == n0
    chosen = select.selected()
    assert set(chosen["ssd_chunk"]) == {"xla"} and "ssm2_step" in chosen
