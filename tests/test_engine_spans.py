"""The serving tick seen from inside the program: the engine row's phase
spans and their ``pt.*`` trace annotations, the one clock they share with the
request marks, the always-live counters of work done, and the O(1) span
budget of a request. Tiny paged server on the CPU."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.scheduler import Scheduler
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.inference.transport import CountingClock
from paddle_tpu.telemetry import (DEVICE_QUEUE_SPANS, ENGINE_RID, NULL_PHASE,
                                  ServingTelemetry)

# children of a tick, in the order a tick runs them; first_token_wait is the
# one grandchild (inside ``prefill``)
PHASES = ("admit", "prefill", "decode_dispatch", "decode_wait", "harvest")
COUNTERS = ("serving_tokens_emitted", "serving_decode_rows",
            "serving_decode_ctx", "serving_prefill_tokens",
            "serving_prefill_chunks", "serving_prefill_ctx")


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=160,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg)


def _server(model, **kw):
    kw.setdefault("max_batch", 3)
    return GenerationServer(model, max_len=96, cache="paged", block_size=8,
                            prefill_chunk=16, **kw)


def _submit(srv, lens=(21, 40, 18, 27, 9), max_new=6):
    rng = np.random.RandomState(11)
    return [srv.submit(rng.randint(1, 128, (n,)).tolist(),
                       max_new_tokens=max_new) for n in lens]


def _inside(child, parent):
    return (child["t0"] >= parent["t0"]
            and child["t0"] + child["dur"] <= parent["t0"] + parent["dur"])


# ------------------------------------------------------------ (a) span tree
def test_every_tick_holds_its_phases_in_order_without_overlap(model):
    srv = _server(model, telemetry=True)
    rids = _submit(srv)
    srv.run()
    tr = srv.telemetry.tracer
    spans = tr.spans(ENGINE_RID)
    ticks = [s for s in spans if s["name"] == "tick"]
    flight = srv.telemetry.flight.dump()
    assert [t["args"]["seq"] for t in ticks] == [r["seq"] for r in flight]
    assert {s["name"] for s in spans} == set(PHASES) | {"tick",
                                                        "first_token_wait"}
    for t, rec in zip(ticks, flight):
        seq = t["args"]["seq"]
        mine = [s for s in spans if s["args"].get("tick") == seq]
        assert mine and all(_inside(s, t) for s in mine)
        kids = [s for s in mine if s["name"] in PHASES]
        assert [k["name"] for k in kids] == [p for p in PHASES if p in
                                             {k["name"] for k in kids}]
        for a, b in zip(kids, kids[1:]):
            assert a["t0"] + a["dur"] <= b["t0"]
        assert t["dur"] >= sum(k["dur"] for k in kids)
        waits = [s for s in mine if s["name"] == "first_token_wait"]
        pf = next(k for k in kids if k["name"] == "prefill")
        # a chunk dispatched on its own is waited for inside ``prefill``;
        # one that rode in the decode trip's call after the tick's harvest
        rode = [w for w in waits if not _inside(w, pf)]
        assert len(rode) <= pf["args"]["riding"]
        for w in rode:
            assert w["t0"] >= kids[-1]["t0"] + kids[-1]["dur"]
            assert kids[-1]["name"] == "harvest"
        for a, b in zip(waits, waits[1:]):
            assert a["t0"] + a["dur"] <= b["t0"]
        # the flight record's wait_s is the tick's two waits, and its wall
        # the tick span
        dw = [k["dur"] for k in kids if k["name"] == "decode_wait"]
        assert rec["wait_s"] == pytest.approx(
            sum(dw) + sum(w["dur"] for w in waits), abs=1e-9)
        assert rec["t_wall_s"] == t["dur"]
        assert (t["args"]["prog"], t["args"]["chunks"]) == (
            rec["prog"], pf["args"]["chunks"] + pf["args"]["riding"])
        assert ("+chunk" in rec["prog"]) == bool(pf["args"]["riding"])
    # every request's first token was waited for once, under its own rid
    waited = [s["args"]["rid"] for s in spans
              if s["name"] == "first_token_wait"]
    assert sorted(waited) == sorted(rids)
    # a chunk names the tick that dispatched it and says what it timed
    for rid in rids:
        for c in (s for s in tr.spans(rid) if s["name"] == "prefill_chunk"):
            assert c["args"]["dispatch_only"] is True
            t = next(t for t in ticks if t["args"]["seq"] == c["args"]["tick"])
            assert _inside(c, t)
    assert sum(t["args"]["tokens"] for t in ticks) == 5 * 6
    ev = [e for e in tr.chrome_events() if e.get("tid") == ENGINE_RID]
    assert {"name": "engine"} in [e["args"] for e in ev if e["ph"] == "M"]


def test_dense_step_uses_the_same_phases(model):
    srv = GenerationServer(model, max_batch=2, max_len=64, cache="dense",
                           prompt_buckets=(16, 32), telemetry=True)
    _submit(srv, lens=(9, 14), max_new=4)
    srv.run()
    spans = srv.telemetry.tracer.spans(ENGINE_RID)
    assert {s["name"] for s in spans} == {
        "tick", "first_token_wait", "decode_dispatch", "decode_wait",
        "harvest"}
    rec = srv.telemetry.flight.dump()[0]
    assert rec["prog"] == "dense" and 0 < rec["wait_s"] < rec["t_wall_s"]


# --------------------------------------------------------------- (b) counters
@pytest.mark.parametrize("telemetry", [None, True], ids=["off", "on"])
def test_counters_equal_the_benchmarks_progress_step_for_step(model,
                                                              telemetry):
    from benchmarks.drivers.serve_paged import _Progress
    from benchmarks.roofline.prefill_attention import attended

    srv = _server(model, telemetry=telemetry)
    reg = srv.telemetry.registry
    prog = _Progress(srv)
    prompt_len, pending = {}, [(21, 6), (40, 9), (18, 2), (27, 5), (9, 12),
                               (33, 3)]
    rng = np.random.RandomState(5)
    seen = dict.fromkeys(COUNTERS, 0)
    steps = completions = 0
    remaining = 1
    while remaining or pending:
        if pending and steps % 2 == 0:       # arrivals while others decode
            n, new = pending.pop(0)
            rid = srv.submit(rng.randint(1, 128, (n,)).tolist(),
                             max_new_tokens=new)
            prompt_len[rid] = n
        remaining = srv.step()
        done = srv.take_results()
        completions += len(done)
        rec = {"tokens": 0, "decode_rows": 0, "decode_ctx": 0,
               "prefill_chunks": []}
        prog.step(done, prompt_len, rec)
        now = {c: int(reg.get(c).total()) for c in COUNTERS}
        delta = {c: now[c] - seen[c] for c in COUNTERS}
        seen = now
        chunks = rec["prefill_chunks"]
        assert delta == {
            "serving_tokens_emitted": rec["tokens"],
            "serving_decode_rows": rec["decode_rows"],
            "serving_decode_ctx": rec["decode_ctx"],
            "serving_prefill_tokens": sum(n for _, n in chunks),
            "serving_prefill_chunks": len(chunks),
            "serving_prefill_ctx": sum(attended(s, n) for s, n in chunks),
        }, f"step {steps}"
        steps += 1
    assert completions == 6 and seen["serving_prefill_chunks"] >= 10
    assert seen["serving_tokens_emitted"] == 6 + 9 + 2 + 5 + 12 + 3
    assert seen["serving_decode_rows"] == seen["serving_tokens_emitted"] - 6
    assert srv._prefill_tokens == seen["serving_prefill_tokens"] == sum(
        prompt_len.values())


def test_a_window_cut_short_counts_only_the_tokens_it_folded(model):
    """tick_window 4, answers of 6: the second window folds one token of its
    four, and the counters take the other three off again."""
    srv = _server(model, tick_window=4)
    _submit(srv, lens=(9,), max_new=6)
    srv.run()
    reg = srv.telemetry.registry
    assert reg.get("serving_tokens_emitted").total() == 6
    assert reg.get("serving_decode_rows").total() == 5
    assert reg.get("serving_decode_ctx").total() == sum(range(10, 15))


# ------------------------------------------------------------- (c) one clock
def test_first_token_mark_and_instant_share_the_default_clock(model):
    srv = _server(model, telemetry=True)
    assert srv.telemetry.clock is srv._wall is srv.telemetry.tracer.clock
    rids = _submit(srv, lens=(21, 9))
    srv.run()
    for rid in rids:
        (inst,) = [s for s in srv.telemetry.tracer.spans(rid)
                   if s["name"] == "first_token"]
        mark = srv.request_metrics()[rid]["first_token_t"]
        assert abs(inst["t0"] - mark) < 1e-3
        assert abs(mark - time.monotonic()) < 600      # it IS that clock


def test_first_token_mark_and_instant_identical_on_an_injected_clock(model):
    srv = _server(model, telemetry=True, clock=CountingClock())
    rids = _submit(srv, lens=(21, 9))
    srv.run()
    for rid in rids:
        (inst,) = [s for s in srv.telemetry.tracer.spans(rid)
                   if s["name"] == "first_token"]
        assert inst["t0"] == srv.request_metrics()[rid]["first_token_t"]
    # a facade that brings its own clock keeps its own readings
    own = ServingTelemetry(clock=CountingClock(start=1e6))
    srv = _server(model, telemetry=own, clock=CountingClock())
    (rid,) = _submit(srv, lens=(9,))
    srv.run()
    (inst,) = [s for s in own.tracer.spans(rid) if s["name"] == "first_token"]
    assert inst["t0"] > 1e6 > srv.request_metrics()[rid]["first_token_t"]


def test_an_evacuated_request_closes_its_decode_bracket_as_migrated(model):
    srv = _server(model, telemetry=True)
    (rid, queued) = _submit(srv, lens=(21, 9, 9, 9, 9), max_new=30)[0::4]
    for _ in range(4):
        srv.step()
    assert srv.status(rid) == "running" and srv.status(queued) == "queued"
    srv.evacuate()
    tr = srv.telemetry.tracer
    row = tr.spans(rid)
    (dec,) = [s for s in row if s["name"] == "decode"]
    assert dec["args"]["outcome"] == "migrated"
    assert row[-1]["name"] == "migrated"
    assert [s["name"] for s in tr.spans(queued)] == ["queued", "migrated"]
    assert tr.open_spans(rid) == tr.open_spans(queued) == []


# ---------------------------------------------------- (d) trace annotations
class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _Recorder.log.append(("enter", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name, self.kw))
        return False


class _CountedClock:
    def __init__(self):
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return time.monotonic()


def test_enabled_telemetry_emits_nested_pt_annotations(model, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    srv = _server(model, telemetry=True)
    _submit(srv, lens=(21, 9))
    srv.run()
    # (the device-queue row's two cross phases and ticks by design and are
    # held by tests/test_device_queue.py)
    queue = {"pt." + n for n in DEVICE_QUEUE_SPANS}
    assert {e[1] for e in _Recorder.log} >= queue
    log = [e for e in _Recorder.log if e[1].startswith("pt.")
           and e[1] not in queue]
    assert {e[1] for e in log} == {"pt." + p for p in PHASES} | {
        "pt.tick", "pt.first_token_wait"}
    stack, parents = [], {}
    for kind, name, kw in log:
        if kind == "enter":
            if stack:
                assert kw["tick"] == stack[0][1]["tick"]
            parents.setdefault(name, set()).add(stack[-1][0] if stack
                                                else None)
            stack.append((name, kw))
        else:
            assert stack.pop() == (name, kw)
    assert not stack
    assert parents.pop("pt.tick") == {None}
    # (inside ``prefill`` for a chunk of its own, straight under the tick for
    # one that rode in the decode trip's call)
    assert parents.pop("pt.first_token_wait") == {"pt.prefill", "pt.tick"}
    assert all(p == {"pt.tick"} for p in parents.values()), parents
    seqs = [kw["tick"] for kind, name, kw in log
            if kind == "enter" and name == "pt.tick"]
    assert seqs == list(range(len(seqs)))


def test_disabled_telemetry_annotates_nothing_and_reads_no_new_clock(
        model, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    clock = _CountedClock()
    # (the scheduler keeps a clock of its own, so every read counted here
    # is the engine's)
    srv = _server(model, clock=clock, policy=Scheduler())
    assert srv.telemetry.phase("tick", 0) is NULL_PHASE
    rids = _submit(srv, lens=(21, 40, 9))
    assert clock.calls == len(rids)                    # submit_t marks
    reg = srv.telemetry.registry
    in_steps = 0
    remaining = 1
    while remaining:
        before = clock.calls
        remaining = srv.step()
        in_steps += clock.calls - before
    assert _Recorder.log == []
    # what the tick read before this PR and nothing else: two reads around
    # every chunk dispatch (the prefill-throughput ledger), one first-token
    # mark and one done mark a request
    chunks = int(reg.get("serving_prefill_chunks").total())
    assert chunks == 2 + 3 + 1
    assert in_steps == 2 * chunks + 2 * len(rids)
    assert srv.telemetry.tracer.spans() == []


# ------------------------------------------------------------ (e) span budget
def test_long_full_batch_run_drops_no_span(model):
    """64 slots kept full for 1200 ticks: a span per decoding request per
    tick would be 76,800 spans against the tracer's 65,536. The decode
    program is replaced by a constant (the test is about the host's spans)."""
    B = 64
    srv = GenerationServer(model, max_batch=B, max_len=160, cache="paged",
                           block_size=16, prefill_chunk=16, telemetry=True)

    def fake_decode(params, tokens, pools, *rest):
        return np.ones((1, B), np.int32), pools, rest[-2]   # + slot pools

    srv._decode_paged = fake_decode
    rng = np.random.RandomState(3)
    ticks = submitted = 0
    while ticks < 1200:
        while srv.load_metrics()["queue_depth"] < 4:
            srv.submit(rng.randint(1, 128, (8,)).tolist(),
                       max_new_tokens=int(rng.randint(60, 140)))
            submitted += 1
        srv.step()
        srv.take_results()
        ticks += 1
    tr = srv.telemetry.tracer
    assert tr.dropped == 0
    assert srv.telemetry.flight.total == 1200
    # (a slot whose answer ends sits out the one trip that is dispatched
    # before its last tokens are read: an answer a slot every ~100 ticks
    # keeps a few of the 64 rows out of most trips)
    full = [s for s in tr.spans(ENGINE_RID) if s["name"] == "decode_dispatch"
            and s["args"]["rows"] >= B - 4]
    assert len(full) > 1000
    per_request = (len(tr.spans()) - len(tr.spans(ENGINE_RID))) / submitted
    assert per_request < 12
    assert len(tr.spans()) < 20_000
