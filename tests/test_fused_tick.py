"""A prompt chunk rides in the decode trip's program call
(``PagedExecutor._decode_chunk_fn``, ``GenerationServer._plain_decode_trip``):
one call a tick reads the weights once for the decode rows AND one chunk.

Held here, on a tiny Llama (and a tiny SambaY for the class that keeps its own
chunk program) on the CPU: the joint program emits token for token what the
two-program path emits — the same server, steered in the test to take
``_chunk_prefill_fn`` for every chunk; a final chunk's slot decodes from the
next tick; the two counters partition ``serving_prefill_chunks``; whatever
reads or moves slot state finds a retired engine with a joint trip pending;
the servers that cannot take the joint step say why and dispatch the chunk
program; and the benchmark's warm-up compiles everything a window will use.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.executor import PagedExecutor
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.ops import select

from benchmarks.drivers import serve_hybrid

V = 128
REASONS = ("no_decoding_row", "second_chunk", "slot_state", "cp", "spec",
           "tick_window", "lora", "moe", "model", "two_programs")
# the tiny SambaY of tests/test_phi4flash_serving.py: all five layer kinds
TINY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": 24, "mb_per_layer": 2,
    "layer_norm_eps": 1e-5, "max_position_embeddings": 4096,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "initializer_range": 0.15,
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 4},
}


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


@pytest.fixture(scope="module")
def llama():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=160,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg)


def _server(model, two_programs=False, **kw):
    """``two_programs``: the same server made to run every chunk through
    ``_chunk_prefill_fn`` and today's order — the path this PR's servers
    fall back to — by answering the executor's one question for it."""
    kw = {"max_batch": 4, "max_len": 96, "block_size": 8,
          "prefill_chunk": 16, **kw}
    if not two_programs:
        return GenerationServer(model, cache="paged", **kw)
    asked = PagedExecutor._why_chunks_run_alone
    PagedExecutor._why_chunks_run_alone = lambda self: "two_programs"
    try:
        return GenerationServer(model, cache="paged", **kw)
    finally:
        PagedExecutor._why_chunks_run_alone = asked


def _prompt(n, seed, vocab=V):
    return np.random.RandomState(seed).randint(1, vocab, (n,)).tolist()


def _counter(srv, name, **where):
    c = srv.telemetry.registry.get(name)
    return int(c.total(where=where) if where else c.total())


def _chunks(srv):
    """(all, fused, alone by reason): the three counters."""
    alone = {r: _counter(srv, "serving_prefill_chunks_alone", reason=r)
             for r in REASONS}
    return (_counter(srv, "serving_prefill_chunks"),
            _counter(srv, "serving_prefill_chunks_fused"),
            {r: n for r, n in alone.items() if n})


def _partitioned(srv):
    total, fused, alone = _chunks(srv)
    assert fused + sum(alone.values()) == total
    assert sum(alone.values()) == _counter(srv, "serving_prefill_chunks_alone")
    return total, fused, alone


def _drive(srv, arrivals, check=None, **submit_kw):
    """Offer ``arrivals`` — (step, prompt, max_new) — and step ``srv`` dry.
    Returns {index: tokens}."""
    rid_of, out, step = {}, {}, 0
    remaining = 1
    while remaining or len(rid_of) < len(arrivals):
        for i, (at, prompt, new) in enumerate(arrivals):
            if at == step:
                rid_of[i] = srv.submit(prompt, max_new_tokens=new,
                                       **submit_kw)
        remaining = srv.step()
        if check is not None:
            check(srv, step)
        out.update(srv.take_results())
        step += 1
    assert srv.step() == 0 and srv._trips == [] and srv._rider is None
    return {i: out[r] for i, r in rid_of.items()}


# prompts of one to five chunks of 16 arriving while others decode
MEETS = [(0, _prompt(9, 1), 30), (2, _prompt(40, 2), 20),
         (3, _prompt(21, 3), 24), (9, _prompt(70, 4), 12)]
# two (then three) prompts admitted in ONE tick beside a decoding row
TOGETHER = [(0, _prompt(12, 5), 40), (3, _prompt(37, 6), 10),
            (3, _prompt(50, 7), 10), (3, _prompt(20, 8), 10)]
CASES = {
    "a_chunk_meets_decoding_rows": (MEETS, {}),
    "two_prefilling_slots_in_one_tick": (TOGETHER, {}),
    "int8_kv": (MEETS, {"kv_quant": "int8"}),
    "int8_kv_two_prefilling_slots": (TOGETHER, {"kv_quant": "int8"}),
    "full_batch_and_a_queue": (MEETS + TOGETHER, {"max_batch": 3}),
    "eos": (MEETS, {"eos_token_id": 5}),
    "tp2_mesh": (MEETS, {"mesh": "tp=2"}),
}


# ---------------------------------------- token for token the two programs'
@pytest.mark.parametrize("case", sorted(CASES))
def test_joint_program_emits_what_the_two_program_path_emits(llama, case):
    arrivals, kw = CASES[case]
    ref = _server(llama, two_programs=True, **kw)
    assert ref._decode_chunk is None and ref._chunk_prefill is not None
    want = _drive(ref, arrivals)
    srv = _server(llama, **kw)
    assert srv._chunk_prefill is None          # two programs, not three
    got = _drive(srv, arrivals, check=lambda s, _: s.assert_conserved())
    assert got == want
    total, fused, alone = _partitioned(srv)
    assert total == _counter(ref, "serving_prefill_chunks")
    assert _chunks(ref)[2] == {"two_programs": total}
    # the first prompt of a run has nothing to ride with; of the chunks of
    # one tick one rides; where prompts come one at a time most chunks ride
    assert alone.get("no_decoding_row", 0) >= 1
    assert set(alone) <= {"no_decoding_row", "second_chunk"}
    if arrivals is TOGETHER:
        assert alone["second_chunk"] >= 3 and fused >= 3
    else:
        assert fused >= total // 2
    assert srv.alloc.blocks_in_use == 0


def test_generate_agrees(llama):
    """Chunked prefill through the joint program against ``generate()``."""
    got = _drive(_server(llama), MEETS)
    for i, (_, prompt, new) in enumerate(MEETS):
        ids = llama.generate(paddle.to_tensor(np.array([prompt])),
                             max_new_tokens=new)
        assert np.asarray(ids.value)[0].tolist() == got[i]


# ------------------------------- a final chunk, its first token, its tick
def test_a_final_chunk_rides_and_its_slot_decodes_from_the_next_tick(llama):
    srv = _server(llama)
    a = srv.submit(_prompt(9, 1), max_new_tokens=40)
    for _ in range(3):
        srv.step()
    b = srv.submit(_prompt(30, 2), max_new_tokens=8)       # two chunks
    srv.step()                                             # chunk 1 rides
    slot = next(s for s, r in enumerate(srv._slots)
                if r is not None and r.rid == b)
    req = srv._slots[slot]
    assert _chunks(srv)[1] == 1 and srv._prefilling[slot]
    assert req.pf_next == 16 and req.generated == []
    srv.step()                                             # the final chunk
    assert _chunks(srv)[1] == 2
    # its first token was read from the call's logits row, and the trip
    # that carried the chunk had no row for it
    assert srv._prefilling[slot] is None and len(req.generated) == 1
    assert srv._trips[-1].mask[slot] == 0
    assert int(srv.pos[slot]) == 30
    srv.step()                                             # it decodes
    assert srv._trips[-1].mask[slot] == 1
    out = srv.run()
    ref = _server(llama, two_programs=True)
    ra, rb = (ref.submit(_prompt(9, 1), max_new_tokens=40),
              ref.submit(_prompt(30, 2), max_new_tokens=8))
    want = ref.run()
    assert out[a] == want[ra] and out[b] == want[rb]
    assert srv.request_metrics()[b]["n_generated"] == 8


def test_a_lone_prompt_is_not_held_back(llama):
    """Nothing decodes: the chunk runs at once (the joint program, every
    row masked) and its slot joins the decode rows in that very tick."""
    srv = _server(llama)
    rid = srv.submit(_prompt(9, 1), max_new_tokens=5)
    srv.step()
    assert _chunks(srv) == (1, 0, {"no_decoding_row": 1})
    assert len(srv._trips) == 1 and srv._trips[0].rows == [0]
    assert len(srv.run()[rid]) == 14


# ----------------------- what reads or moves slot state, a joint trip pending
LONG = [(0, _prompt(21, 1), 40), (0, _prompt(9, 3), 40),
        (4, _prompt(60, 2), 30)]


@pytest.mark.parametrize("victim", ["a_decoding_row", "the_prefilling_slot"])
def test_preempt_with_a_joint_trip_pending(llama, victim):
    want = _drive(_server(llama, two_programs=True), LONG)
    hit = []

    def preempt(srv, step):
        if step == 6:
            fused0 = _chunks(srv)[1]
            assert srv._trips and fused0 >= 2 and srv._prefilling[2]
            assert srv._slots[2].pf_next == 48          # 3 of 4 chunks ran
            assert srv._preempt_slot(0 if victim == "a_decoding_row" else 2)
            assert srv._trips == []
            hit.append(step)
        srv.assert_conserved()

    srv = _server(llama)
    got = _drive(srv, LONG, check=preempt)
    assert got == want and hit == [6]
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="preempt") == 1
    if victim == "a_decoding_row":
        assert _counter(srv, "serving_preemptions") == 1
        assert _counter(srv, "serving_resumes") == 1
    else:
        # its prefill starts over, and finds the three chunks it had
        # published in the prefix cache: one more chunk, not four
        assert _counter(srv, "serving_prefill_aborts") == 1
        assert _partitioned(srv)[0] == 2 + 1 + 3 + 1
    _partitioned(srv)


def test_snapshot_and_restore_with_a_joint_trip_pending(llama):
    want = _drive(_server(llama, two_programs=True), LONG)
    srv = _server(llama)
    rids = {}
    for step in range(7):
        for i, (at, prompt, new) in enumerate(LONG):
            if at == step:
                rids[i] = srv.submit(prompt, max_new_tokens=new)
        srv.step()
    assert srv._trips and srv._prefilling[2] and _chunks(srv)[1] >= 2
    snap = srv.snapshot()
    assert srv._trips == []
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="snapshot") == 1
    fresh = _server(llama)
    fresh.restore(snap)
    out = fresh.run()
    assert [out[rids[i]] for i in range(3)] == [want[i] for i in range(3)]
    # the captured server goes on as if nothing had happened
    out = srv.run()
    assert [out[rids[i]] for i in range(3)] == [want[i] for i in range(3)]
    srv.assert_conserved()


def test_pool_pressure_preempts_the_waiting_chunks_slot(llama):
    """A pool too small for all three: a reservation made after the chunk
    was laid out may take its slot — the chunk is dropped, not dispatched
    into blocks that were given back."""
    kw = {"num_blocks": 15, "max_batch": 3}
    want = _drive(_server(llama, two_programs=True, **kw), LONG)
    srv = _server(llama, **kw)
    got = _drive(srv, LONG, check=lambda s, _: s.assert_conserved())
    assert got == want
    assert _counter(srv, "serving_preemptions") \
        + _counter(srv, "serving_prefill_aborts") >= 1
    _partitioned(srv)
    assert want == _drive(_server(llama, max_batch=3), LONG)


# ----------------------------- the servers that keep the chunk program
def _fallbacks(llama):
    from paddle_tpu.inference.speculative import SpecConfig

    return {
        "slot_state": lambda: (serve_hybrid.build_model(TINY, seed=11)[0],
                               {}, TINY["vocab_size"]),
        "cp": lambda: (llama, {"mesh": "cp=2"}, V),
        "spec": lambda: (llama, {"spec": SpecConfig(k=3, drafter="ngram")},
                         V),
        "tick_window": lambda: (llama, {"tick_window": 4}, V),
    }


@pytest.mark.parametrize("why", ["slot_state", "cp", "spec", "tick_window"])
def test_a_server_that_cannot_take_the_joint_step_says_why(llama, why):
    model, kw, vocab = _fallbacks(llama)[why]()
    srv = _server(model, **kw)
    assert srv._exec.chunk_alone_why == why and srv._decode_chunk is None
    body = srv._exec.chunk_prefill.__wrapped__
    if srv._exec.mesh is not None:          # traced inside the mesh context
        body = body.__wrapped__
    assert body.__func__ is PagedExecutor._chunk_prefill_fn
    arrivals = [(at, _prompt(len(p), 10 + i, vocab), new)
                for i, (at, p, new) in enumerate(MEETS)]
    got = _drive(srv, arrivals)
    total, fused, alone = _partitioned(srv)
    assert total == 1 + 3 + 2 + 5 and fused == 0 and alone == {why: total}
    for i, (_, prompt, new) in enumerate(arrivals):
        assert got[i][:len(prompt)] == prompt
        assert len(got[i]) <= len(prompt) + new


def test_adapters_keep_the_chunk_program(llama):
    from paddle_tpu.inference.lora import AdapterRegistry, LoRAConfig

    srv = _server(llama, lora=LoRAConfig(AdapterRegistry(),
                                         max_live_adapters=2, max_rank=2))
    assert srv._exec.chunk_alone_why == "lora"
    _drive(srv, MEETS[:2])
    assert _partitioned(srv) == (4, 0, {"lora": 4})


# ------------------------------------------ the benchmark's warm-up suffices
@pytest.mark.parametrize("kw", [{}, {"kv_quant": "int8"}, {"mesh": "tp=2"}],
                         ids=["bf16_kv", "int8_kv", "tp2_mesh"])
def test_the_benchmarks_warm_up_compiles_all_a_window_uses(llama, kw):
    """``benchmarks/drivers/serve_paged.py::measure`` warms with two prompts
    of ``prefill_chunk + 9`` tokens, 4 new tokens each, drained. After it a
    chunk that meets decoding rows, two chunks in a tick, a decode-only tick
    and a masked chunk compile nothing: the joint program is THE program of
    every chunk, the decode program the other."""
    from paddle_tpu.analysis.recompile_guard import compile_count

    srv = _server(llama, **kw)
    rng = np.random.default_rng(0)
    for _ in range(2):
        srv.submit(rng.integers(1, V, size=srv.prefill_chunk + 9).tolist(),
                   max_new_tokens=4, temperature=0.0)
    srv.run()
    warm = _chunks(srv)
    assert warm[0] == 4
    before = compile_count()
    got = _drive(srv, MEETS + TOGETHER)
    assert compile_count() == before
    total, fused, alone = _partitioned(srv)
    assert fused - warm[1] >= 8 and alone["second_chunk"] >= 3
    assert len(got) == len(MEETS) + len(TOGETHER)
