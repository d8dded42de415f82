"""The pending decode trip: the paged engine dispatches trip N+1 before it
reads trip N (``GenerationServer._plain_decode_trip`` / ``_retire_pending``).

Held here, on a tiny Llama and a tiny SambaY on the CPU: the pipelined loop
emits token for token what a loop that retires every trip at once emits; the
state ``save_slot`` hands out is that of exactly the tokens the engine holds;
everything that reads or moves per-slot state finds a retired engine; the
three counters say how often the mechanism engaged; and the engine row's
spans keep their shape for the benchmark's readers.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.scheduler import PRIORITY_NORMAL
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.ops import select
from paddle_tpu.telemetry import ENGINE_RID

from benchmarks import span_readers
from benchmarks.drivers import serve_hybrid

V = 128
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
PHASES = ("admit", "prefill", "decode_dispatch", "decode_wait", "harvest")


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


@pytest.fixture(scope="module")
def sambay():
    return serve_hybrid.build_model(TINY, seed=11)[0]


@pytest.fixture(scope="module")
def models(llama, sambay):
    return {"llama": llama, "sambay": sambay}


def _server(model, **kw):
    kw = {"max_batch": 4, "max_len": 96, "block_size": 8,
          "prefill_chunk": 16, **kw}
    return GenerationServer(model, cache="paged", **kw)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, V, (n,)).tolist()


def _counter(srv, name, **where):
    c = srv.telemetry.registry.get(name)
    return int(c.total(where=where) if where else c.total())


def _trips(srv):
    return (_counter(srv, "serving_decode_trips_overlapped")
            + _counter(srv, "serving_decode_trips_retired_early"))


# what the loops of (a) are offered: (arrives at step, prompt, max_new). The
# second ends by max_len (prompt + max_new == max_len), the fourth arrives
# while a trip is pending and before any other has ended, so that it takes
# the same slot — and a sampled row the same noise — in both loops
ARRIVALS = [(0, _prompt(21, 1), 17), (0, _prompt(70, 2), 26),
            (0, _prompt(9, 3), 30), (3, _prompt(13, 4), 22)]


def _drive(srv, at_once, arrivals=ARRIVALS, temperature=0.0, check=None):
    """Offer ``arrivals`` and step ``srv`` dry. ``at_once``: retire every
    trip right after its step — the reference loop. Returns {index: tokens}
    and the steps it took."""
    rid_of, out, step = {}, {}, 0
    remaining = 1
    while remaining or len(rid_of) < len(arrivals):
        for i, (at, prompt, new) in enumerate(arrivals):
            if at == step:
                rid_of[i] = srv.submit(prompt, max_new_tokens=new,
                                       temperature=temperature, top_k=20)
        remaining = srv.step()
        if at_once:
            srv._retire_pending("test")
            lm = srv.load_metrics()
            remaining = lm["slots_occupied"] + lm["queue_depth"]
        if check is not None:
            check(srv, step)
        out.update(srv.take_results())
        step += 1
    assert srv.step() == 0 and srv._trips == []
    return {i: out[r] for i, r in rid_of.items()}, step


# -------------------------------------------- (a) token for token the same
@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", ["llama", "sambay"])
def test_pipelined_loop_emits_what_a_retire_at_once_loop_emits(
        models, name, temperature, window):
    model = models[name]
    want, _ = _drive(_server(model, tick_window=window, seed=5), True,
                     temperature=temperature)
    srv = _server(model, tick_window=window, seed=5)
    got, _ = _drive(srv, False, temperature=temperature,
                    check=lambda s, _: s.assert_conserved())
    assert got == want
    for i, (_, prompt, new) in enumerate(ARRIVALS):
        assert got[i][:len(prompt)] == prompt
        assert len(got[i]) == len(prompt) + new
    assert len(got[1]) == srv.max_len            # the one that ends by max_len
    # it did overlap: all but the trips that had nothing behind them
    assert _counter(srv, "serving_decode_trips_overlapped") \
        >= _trips(srv) - 3
    assert _counter(srv, "serving_decode_rows_discarded") == 0


@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("name", ["llama", "sambay"])
def test_eos_in_the_middle_of_a_pending_trip(models, name, window):
    """The eos is read a trip late: the row runs on into the trip that was
    dispatched meanwhile, whose tokens are discarded; the others' tokens and
    the pool do not notice."""
    model = models[name]
    plain, _ = _drive(_server(model, tick_window=window), False)
    # a token that request 2 emits mid-answer and mid-window, and no other
    # request emits before it has ended anyway
    n2 = len(ARRIVALS[2][1])
    at = next(j for j in range(6, 26)
              if (j - 1) % 4 not in (0, 3)
              and plain[2][n2 + j] not in plain[2][n2:n2 + j])
    eos = plain[2][n2 + at]
    want, _ = _drive(_server(model, tick_window=window, eos_token_id=eos),
                     True)
    srv = _server(model, tick_window=window, eos_token_id=eos)
    got, _ = _drive(srv, False, check=lambda s, _: s.assert_conserved())
    assert got == want
    assert got[2] == plain[2][:n2 + at + 1] and got[2][-1] == eos
    ends = sum(1 for i, (_, p, new) in enumerate(ARRIVALS)
               if got[i][-1] == eos and len(got[i]) < len(p) + new)
    assert ends >= 1
    discarded = _counter(srv, "serving_decode_rows_discarded")
    if window == 1:
        # one token a trip: the host sees the eos before the trip after the
        # next, so an eos end costs the one row the parent's did
        assert discarded == ends
    else:
        # the rest of its window, and the whole window dispatched meanwhile
        assert discarded >= ends * (window + 1)
    assert srv.alloc.blocks_in_use == 0


def test_run_drains_and_a_lone_request_is_not_held_back(llama):
    srv = _server(llama)
    rid = srv.submit(_prompt(9, 1), max_new_tokens=5)
    steps = 0
    while srv.step():
        steps += 1
    # chunk + first token + trip 1 in the first step, trips 2..4 in the next
    # three, and one more step that only retires the last
    assert steps == 4 and srv._trips == []
    assert len(srv.take_results()[rid]) == 14
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="idle") == 1
    assert _counter(srv, "serving_decode_trips_overlapped") == 3


@pytest.mark.parametrize("mesh", [None, "tp=2"])
def test_one_compiled_decode_variant_pending_or_not(llama, mesh):
    """The first trip takes a stand-in for the pending stack, every later
    one a real stack: one program, compiled in the first step (greedy, one
    trip length) — on one chip and placed on a tp mesh."""
    from paddle_tpu.analysis.recompile_guard import compile_count

    srv = _server(llama, mesh=mesh)
    srv.submit(_prompt(9, 1), max_new_tokens=12)
    srv.step()
    after_first = compile_count()
    assert _trips(srv) + len(srv._trips) == 1
    srv.submit(_prompt(20, 2), max_new_tokens=12)
    srv.run()
    assert _counter(srv, "serving_decode_trips_overlapped") >= 10
    assert compile_count() == after_first


# ------------------------------------- (b) the state save_slot hands out
def test_save_slot_returns_the_state_of_the_tokens_the_engine_holds(sambay):
    """After ANY step, for every decoding slot: ``save_slot`` retires the
    pending trip, and what it returns is the state of exactly
    ``pos == len(prompt) + len(generated) - 1`` tokens — bit for bit the
    retire-at-once loop's state at that count."""
    arrivals = [(0, _prompt(30, 5), 40), (0, _prompt(12, 6), 40),
                (2, _prompt(19, 7), 40)]
    ref_state = {}

    def record(srv, _):
        for s, req in enumerate(srv._slots):
            if req is not None and not srv._prefilling[s]:
                ref_state[req.rid, int(srv.pos[s])] = srv._exec.save_slot(s)

    _drive(_server(sambay), True, arrivals, check=record)
    checked = [0, 0]

    def probe(srv, step):
        if step % 3 == 2:                 # the steps between run overlapped
            return
        pending = bool(srv._trips)
        for s in range(srv.max_batch):
            if srv._slots[s] is None or srv._prefilling[s]:
                continue
            arrays = srv._exec.save_slot(s)
            assert srv._trips == []
            req = srv._slots[s]
            if req is None:               # it ended in the trip just retired
                continue
            n = int(srv.pos[s])
            assert n == len(req.prompt) + len(req.generated) - 1
            for got, want in zip(arrays, ref_state[req.rid, n]):
                np.testing.assert_array_equal(got, want)
            checked[0] += 1
        checked[1] += pending
        srv.assert_conserved()

    srv = _server(sambay)
    _drive(srv, False, arrivals, check=probe)
    assert checked[0] > 60 and checked[1] > 20
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="save_slot") == checked[1]


def test_the_benchmarks_probe_finds_its_slots_after_the_loop(sambay):
    """``drivers/serve_hybrid.py::probe_state`` as ``run`` calls it: after
    the loop's last step and the read of the request marks, with a trip
    pending when the loop stopped."""
    srv = _server(sambay)
    prompts = [_prompt(30, 5), _prompt(12, 6), _prompt(19, 7)]
    for p in prompts:
        srv.submit(p, max_new_tokens=60)
    for _ in range(14):
        srv.step()
    assert srv._trips                     # the loop ends with one unread
    srv.request_metrics()                 # (serve_paged.measure, at its end)
    probe = serve_hybrid.probe_state(srv, 2, seed=3, max_tokens=96)
    assert len(probe) == 2
    calm = _server(sambay)
    for p in probe:
        n = len(p["tokens"])
        req = srv._slots[p["slot"]]
        assert p["tokens"] == (req.prompt + req.generated)[:n]
        assert n == int(srv.pos[p["slot"]])
        # the state of a server that fed the same tokens one trip at a time
        rid = calm.submit(req.prompt, max_new_tokens=60)
        while len(calm._slots[0].generated if calm._slots[0] else []) \
                < n - len(req.prompt) + 1:
            calm.step()
            calm._retire_pending("test")
        state = serve_hybrid.probe_state(calm, 1, seed=3, max_tokens=96)
        assert state[0]["tokens"] == p["tokens"]
        for i in p["h"]:
            np.testing.assert_array_equal(p["h"][i], state[0]["h"][i])
        calm.cancel(rid)


# ------------------------- (c) what reads or moves slot state retires first
def _calm(model, arrivals, **kw):
    return _drive(_server(model, **kw), True, arrivals)[0]


LONG = [(0, _prompt(21, 1), 40), (0, _prompt(33, 2), 40),
        (0, _prompt(9, 3), 40)]


@pytest.mark.parametrize("name", ["llama", "sambay"])
def test_preempt_swap_out_and_resume_with_a_trip_pending(models, name):
    model = models[name]
    want = _calm(model, LONG)

    def preempt(srv, step):
        if step == 6:
            assert srv._trips and not srv._prefilling[1]
            assert srv._preempt_slot(1)
            assert srv._trips == []
            req = srv._sched.waiting()[0].req
            assert req.sched.swap.n_tokens == \
                len(req.prompt) + len(req.generated) - 1
        srv.assert_conserved()

    srv = _server(model)
    got, _ = _drive(srv, False, LONG, check=preempt)
    assert got == want
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="preempt") == 1
    assert _counter(srv, "serving_preemptions") == 1
    assert _counter(srv, "serving_resumes") == 1


def test_an_urgent_arrival_preempts_a_full_batch_with_a_trip_pending(llama):
    want = _calm(llama, LONG, max_batch=3, policy="priority")
    srv = _server(llama, max_batch=3, policy="priority")
    urgent = {}

    def arrive(srv, step):
        if step == 5:
            assert srv._trips
            urgent["rid"] = srv.submit(_prompt(11, 9), max_new_tokens=6,
                                       priority=PRIORITY_NORMAL - 1)
        srv.assert_conserved()

    got, _ = _drive(srv, False, LONG, check=arrive)
    assert got == want
    assert _counter(srv, "serving_preemptions") == 1
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="preempt") == 1
    assert srv.request_metrics()[urgent["rid"]]["n_generated"] == 6


def test_pool_pressure_preempts_inside_the_reservation(llama):
    """A pool too small for three answers: the reservation of the coming
    trip swaps a request out, which reads the pending trip first."""
    kw = {"num_blocks": 14, "max_batch": 3}
    want = _calm(llama, LONG, **kw)
    srv = _server(llama, **kw)
    got, _ = _drive(srv, False, LONG,
                    check=lambda s, _: s.assert_conserved())
    assert got == want
    assert _counter(srv, "serving_preemptions") >= 1
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="preempt") >= 1
    assert want == _calm(llama, LONG, max_batch=3)


@pytest.mark.parametrize("name", ["llama", "sambay"])
def test_snapshot_restore_and_evacuate_with_a_trip_pending(models, name):
    model = models[name]
    want = _calm(model, LONG)
    srv = _server(model)
    rids = [srv.submit(p, max_new_tokens=new) for _, p, new in LONG]
    for _ in range(7):
        srv.step()
    assert srv._trips
    snap = srv.snapshot()
    assert srv._trips == []
    for d in snap["requests"]:
        assert d["kv"]["n_tokens"] == \
            len(d["prompt"]) + len(d["generated"]) - 1
    fresh = _server(model)
    fresh.restore(snap)
    out = fresh.run()
    assert [out[r] for r in rids] == [want[i] for i in range(3)]
    # the captured server goes on, and leaves through evacuate mid-flight
    for _ in range(5):
        srv.step()
    assert srv._trips
    moved = srv.evacuate()
    assert srv._trips == [] and srv.load_metrics()["slots_occupied"] == 0
    srv.assert_conserved()
    other = _server(model)
    for d in moved["requests"]:
        other.admit_migrated(d, source_config=moved["config"])
    out = other.run()
    assert [out[r] for r in rids] == [want[i] for i in range(3)]
    other.assert_conserved()
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="snapshot") == 2


def test_cancel_with_a_trip_pending(llama):
    want = _calm(llama, LONG)
    srv = _server(llama)
    rids = [srv.submit(p, max_new_tokens=new) for _, p, new in LONG]
    for _ in range(6):
        srv.step()
    assert srv._trips
    assert srv.cancel(rids[1])
    assert srv._trips == [] and srv.status(rids[1]) == "cancelled"
    srv.assert_conserved()
    out = srv.run()
    assert rids[1] not in out
    assert out[rids[0]] == want[0] and out[rids[2]] == want[2]
    assert srv.alloc.blocks_in_use == 0
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="cancel") == 1
    # a queued request is cancelled without touching the pending trip
    srv = _server(llama, max_batch=2)
    rids = [srv.submit(p, max_new_tokens=new) for _, p, new in LONG]
    for _ in range(4):
        srv.step()
    assert srv._trips and srv.cancel(rids[2]) and srv._trips


def test_a_failed_engine_is_salvaged_from_its_last_harvest(llama):
    """``snapshot(trust_kv=False)`` on a failed engine reads no device: the
    unread trip is forgotten and the replay resumes behind the last token
    the host holds."""
    want = _calm(llama, LONG)
    srv = _server(llama)
    rids = [srv.submit(p, max_new_tokens=new) for _, p, new in LONG]
    for _ in range(7):
        srv.step()
    assert srv._trips
    srv.fail("test")
    snap = srv.snapshot(trust_kv=False)
    assert srv._trips == []
    for d in snap["requests"]:
        assert d["replay"] == (d["prompt"] + d["generated"])[:-1]
    fresh = _server(llama)
    fresh.restore(snap)
    out = fresh.run()
    assert [out[r] for r in rids] == [want[i] for i in range(3)]


# ------------------------------------------------------------ (d) counters
def test_a_steady_loop_overlaps_nearly_every_trip(llama):
    srv = _server(llama, max_len=160)
    rng = np.random.RandomState(3)
    for _ in range(300):
        while srv.load_metrics()["queue_depth"] < 2:
            srv.submit(rng.randint(1, V, (int(rng.randint(8, 40)),)).tolist(),
                       max_new_tokens=int(rng.randint(60, 110)))
        srv.step()
        srv.take_results()
    trips = _trips(srv) + len(srv._trips)
    assert trips >= 295 and len(srv._trips) == 1      # the last one unread
    assert _counter(srv, "serving_decode_trips_overlapped") >= 0.95 * trips
    assert _counter(srv, "serving_decode_trips_retired_early") == 0
    assert _counter(srv, "serving_decode_rows_discarded") == 0


def test_a_speculative_server_retires_every_trip_at_once(llama):
    from paddle_tpu.inference.speculative import SpecConfig

    srv = _server(llama, spec=SpecConfig(k=2, drafter="ngram", gate_ticks=4,
                                         gate_cooldown=2, gate_low=1.5))
    plain = _server(llama)
    rids, want = [], []
    for _, p, new in LONG:
        rids.append(srv.submit(p, max_new_tokens=new))
        want.append(plain.submit(p, max_new_tokens=new))
    out, ref = srv.run(), plain.run()
    assert [out[r] for r in rids] == [ref[r] for r in want]
    dispatched = srv._step_no
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="spec") == dispatched == _trips(srv)
    assert _counter(srv, "serving_decode_trips_overlapped") == 0
    # both kinds of trip were among them: verify windows and gated plain
    assert srv.spec_metrics()["gated_plain_windows"] > 0
    assert srv.spec_metrics()["draft_tokens_proposed"] > 0


def test_a_backoff_tick_and_a_tick_fault_retire_at_once(llama):
    from paddle_tpu.inference.faults import FaultInjector, FaultPlan, FaultSpec

    want = _calm(llama, LONG)
    plan = FaultPlan([FaultSpec(site="tick", at=6)])
    srv = _server(llama, faults=FaultInjector(plan))
    got, _ = _drive(srv, False, LONG, check=lambda s, _: s.assert_conserved())
    assert got == want
    assert _counter(srv, "serving_decode_trips_retired_early",
                    reason="fault") == 1
    assert _counter(srv, "serving_tick_retries") == 1


# --------------------------------------------------------- (e) span shape
def test_spans_of_a_pipelined_run_keep_their_shape(llama):
    srv = _server(llama, telemetry=True)
    rids = [srv.submit(_prompt(n, n), max_new_tokens=12)
            for n in (21, 40, 18, 27, 9, 33)]
    srv.run()
    tr = srv.telemetry.tracer
    spans = tr.spans(ENGINE_RID)
    ticks = [s for s in spans if s["name"] == "tick"]
    flight = srv.telemetry.flight.dump()
    assert [t["args"]["seq"] for t in ticks] == [r["seq"] for r in flight]
    overlapped = 0
    for t, rec in zip(ticks, flight):
        seq = t["args"]["seq"]
        mine = [s for s in spans if s["args"].get("tick") == seq]
        kids = [s for s in mine if s["name"] in PHASES]
        names = [k["name"] for k in kids]
        assert names == [p for p in PHASES if p in names]   # in order, once
        for a, b in zip(kids, kids[1:]):
            assert a["t0"] + a["dur"] <= b["t0"]
        for k in kids:
            assert t["t0"] <= k["t0"]
            assert k["t0"] + k["dur"] <= t["t0"] + t["dur"]
        assert names.count("harvest") <= 1
        assert names.count("decode_wait") == names.count("harvest")
        waits = [s["dur"] for s in mine
                 if s["name"] in ("decode_wait", "first_token_wait")]
        assert rec["wait_s"] == pytest.approx(sum(waits), abs=1e-9)
        # what is waited for and folded is the trip of an EARLIER tick, and
        # both phases name the same one
        by = {k["name"]: k for k in kids}
        if "harvest" in by:
            assert by["harvest"]["args"]["trip"] \
                == by["decode_wait"]["args"]["trip"] < seq
            overlapped += "decode_dispatch" in by
    assert overlapped >= len(ticks) - 4
    assert not [s for s in spans if s["args"].get("tick") == -1]
    assert sum(t["args"]["tokens"] for t in ticks) == 6 * 12

    # the benchmark's three readers, unedited, on this run
    marks = srv.request_metrics()
    t0 = min(marks[r]["first_token_t"] for r in rids)
    run = {"seconds": 1e9,
           "spans": [{"rid": s["rid"], "name": s["name"], "t0": s["t0"],
                      "dur": s["dur"]} for s in tr.spans()],
           "requests": [{"first_token_t": marks[r]["first_token_t"] - t0}
                        for r in rids]}
    host = span_readers.host_ms_per_tick(run)
    gap = span_readers.token_gap_p95_ms(run)
    calls = span_readers.prefill_calls_p95(run)
    assert host > 0 and gap > 0 and calls >= 1
    row = span_readers.window_ticks(run, span_readers.engine_row(run))
    assert len(row) >= len(ticks) - 1      # (the window opens inside one)
    for t in row:
        kinds = [c["name"] for c in t["children"]]
        assert kinds.count("harvest") <= 1 and kinds.count("decode_wait") <= 1


def test_a_retire_between_steps_names_no_tick(llama):
    srv = _server(llama, telemetry=True)
    srv.submit(_prompt(9, 1), max_new_tokens=8)
    for _ in range(3):
        srv.step()
    assert srv._trips
    srv.request_metrics()
    out = [s for s in srv.telemetry.tracer.spans(ENGINE_RID)
           if s["args"].get("tick") == -1]
    assert [s["name"] for s in out] == ["decode_wait", "harvest"]
    assert out[0]["args"]["trip"] == out[1]["args"]["trip"] == 2
