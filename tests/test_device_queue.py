"""What the engine knows of the device's queue: the count of program calls in
flight, the device-queue row's ``starved`` / ``no_work`` spans that open when
a blocking read leaves none, their ``pt.*`` twins, the two counters, and the
two lines ``tools/profile_step.py`` lays them over the device's gaps with.

Tiny models of the three served classes on the CPU — the dense decoder, the
Phi-like hybrid whose chunks run alone, the Granite-like slot-state class
whose chunks ride — on an injected clock that counts its reads, so that "at
its end" and "where the dispatch begins" are exact.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry as T
from paddle_tpu.inference.scheduler import PRIORITY_NORMAL, Scheduler
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.ops import select
from paddle_tpu.telemetry import (DEVICE_QUEUE_RID, DEVICE_QUEUE_SPANS,
                                  ENGINE_RID, ServingTelemetry)

from benchmarks import queue_readers
from benchmarks.drivers import serve_hybrid, serve_ssm_moe
from tests.test_granitemoehybrid_serving import TINY as TINY_GRANITE
from tests.test_pending_trip import TINY as TINY_SAMBAY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import profile_step as P  # noqa: E402

V = 128
NAMES = ["llama", "sambay", "granite"]
TICK_S = 0.001                    # what one read of the injected clock adds


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


@pytest.fixture(scope="module")
def models():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=160,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return {"llama": LlamaForCausalLM(cfg),
            "sambay": serve_hybrid.build_model(TINY_SAMBAY, seed=11)[0],
            "granite": serve_ssm_moe.build_model(TINY_GRANITE, seed=11)[0]}


class _Clock:
    """Every read is 1 ms after the one before: a span's ends say WHICH
    reads they were."""

    def __init__(self):
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return self.calls * TICK_S


def _server(model, clock=None, **kw):
    kw = {"max_batch": 3, "max_len": 96, "block_size": 8,
          "prefill_chunk": 16, **kw}
    if clock is not None:
        # (the scheduler keeps a clock of its own: every read counted is the
        # engine's)
        kw.update(clock=clock, policy=Scheduler())
    return GenerationServer(model, cache="paged", **kw)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, V, (n,)).tolist()


def _queue(srv):
    return srv.telemetry.tracer.spans(DEVICE_QUEUE_RID)


def _engine(srv, name):
    return [s for s in srv.telemetry.tracer.spans(ENGINE_RID)
            if s["name"] == name]


def _inside(t, span):
    return span["t0"] <= t <= span["t1"]


def _log_dispatches(srv, clock):
    """[(program, the clock's reads so far)] where every dispatch begins."""
    log, orig = [], srv._call

    def call(prog, fn, *args):
        log.append((prog, clock.calls))
        return orig(prog, fn, *args)

    srv._call = call
    return log


def _counter(srv, name, **where):
    c = srv.telemetry.registry.get(name)
    return c.total(where=where) if where else c.total()


# --------------------------------------------- (a) the first-token sync
@pytest.mark.parametrize("name", NAMES)
def test_a_final_chunk_opens_starved_at_its_first_token_waits_end(models,
                                                                  name):
    """One request decodes; a second arrives whose prompt is one chunk. Its
    first-token read waits for the call that carried the chunk with nothing
    queued behind it: ``starved`` from the end of that wait to where the next
    dispatch begins — the next tick's trip where the chunk rode, this tick's
    trip where it had a call of its own."""
    clock = _Clock()
    srv = _server(models[name], clock, telemetry=True)
    rides = srv._exec.chunk_alone_why is None
    srv.submit(_prompt(9, 1), max_new_tokens=30)
    for _ in range(4):
        srv.step()
    before = len(_queue(srv))
    log = _log_dispatches(srv, clock)
    late = srv.submit(_prompt(11, 2), max_new_tokens=6)
    srv.step()
    srv.step()
    wait = [s for s in _engine(srv, "first_token_wait")
            if s["args"]["rid"] == late]
    assert len(wait) == 1
    pf = [p for p in _engine(srv, "prefill") if _inside(wait[0]["t0"], p)]
    assert bool(pf) != rides          # a rider's wait lies after the harvest
    new = _queue(srv)[before:]
    assert [s["name"] for s in new] == ["starved"]
    q = new[0]
    assert q["t0"] == wait[0]["t1"]
    assert q["args"]["after"] == "first_token_wait"
    assert q["args"]["tick"] == wait[0]["args"]["tick"]
    # it ends at the first read of the clock as the NEXT dispatch begins
    chunk_call = "decode_chunk" if rides else "chunk_prefill"
    at = [i for i, (prog, _) in enumerate(log) if prog == chunk_call][0]
    nxt_prog, reads = log[at + 1]
    assert q["args"]["prog"] == nxt_prog == "decode_paged"
    assert q["t1"] == pytest.approx((reads + 1) * TICK_S)
    trip = [d for d in _engine(srv, "decode_dispatch")
            if _inside(q["t1"], d)]
    assert len(trip) == 1
    assert trip[0]["args"]["tick"] == q["args"]["tick"] + (1 if rides else 0)
    assert srv._q_open is None and srv.device_calls_in_flight() == 1


# ------------------------------------------------- (b) the steady state
@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("name", NAMES)
def test_the_overlapped_steady_state_opens_none(models, name, window):
    srv = _server(models[name], telemetry=True, tick_window=window)
    for i, n in enumerate((9, 21, 13)):
        srv.submit(_prompt(n, i), max_new_tokens=70)
    while any(srv._prefilling) or None in srv._slots:
        srv.step()
    srv.step()
    before = len(_queue(srv))
    for _ in range(8):
        assert srv.step() == 3
        # trip N went out before trip N-1 was read: one call always in flight
        assert srv.device_calls_in_flight() == 1 and srv._q_open is None
    assert len(_queue(srv)) == before
    srv.run()
    assert srv.device_calls_in_flight() == 0


# --------------------------------------------------- (c) the idle server
@pytest.mark.parametrize("name", NAMES)
def test_a_drained_server_opens_no_work_and_an_arrival_ends_it(models, name):
    clock = _Clock()
    srv = _server(models[name], clock, telemetry=True)
    # nothing dispatched yet: known empty since the server was built
    assert (srv._q_open.name, srv._q_open.args["after"]) == ("no_work",
                                                             "start")
    srv.submit(_prompt(21, 1), max_new_tokens=5)
    srv.run()
    assert srv.device_calls_in_flight() == 0
    q = _queue(srv)
    assert [(s["name"], s["args"]["after"]) for s in q[:2]] == [
        ("no_work", "start"), ("starved", "arrival")]
    assert q[1]["t0"] == q[0]["t1"]
    # the last trip's read left nothing in flight, and its harvest no request
    last_wait = _engine(srv, "decode_wait")[-1]
    open_ = srv._q_open
    assert (open_.name, open_.t0) == ("no_work", last_wait["t1"])
    assert open_.args["after"] == "decode_wait"
    n = len(q)
    for _ in range(3):
        assert srv.step() == 0
    assert len(_queue(srv)) == n and srv._q_open is open_
    # no ``starved`` began at a read that left the server empty
    assert all(s["t0"] != last_wait["t1"] for s in q)
    reads = clock.calls
    srv.submit(_prompt(9, 2), max_new_tokens=3)
    idle = _queue(srv)[-1]
    assert idle["name"] == "no_work" and idle["t0"] == last_wait["t1"]
    assert reads * TICK_S < idle["t1"] <= clock.calls * TICK_S
    assert (srv._q_open.name, srv._q_open.t0, srv._q_open.args["after"]) == (
        "starved", idle["t1"], "arrival")
    srv.run()
    assert srv._q_open.name == "no_work"
    assert srv.device_calls_in_flight() == 0


def test_a_static_cache_server_starves_the_device_every_tick(models):
    srv = GenerationServer(models["llama"], max_batch=2, max_len=64,
                           prompt_buckets=(16, 32), telemetry=True)
    srv.submit(_prompt(9, 1), max_new_tokens=6)
    srv.run()
    waits = _engine(srv, "decode_wait")
    q = [s for s in _queue(srv) if s["args"]["after"] == "decode_wait"]
    # dispatch, read, fold, dispatch: every read but the last left work
    assert [s["t0"] for s in q] == [w["t1"] for w in waits[:-1]]
    assert {s["name"] for s in q} == {"starved"}
    assert {s["args"]["prog"] for s in q} == {"decode_dense"}
    assert srv._q_open.name == "no_work"
    assert srv._q_open.t0 == waits[-1]["t1"]
    assert srv.device_calls_in_flight() == 0


# ------------------------------------------------------ (d) early retires
LONG = [(0, _prompt(21, 1), 40), (0, _prompt(33, 2), 40),
        (0, _prompt(9, 3), 40)]


def _drive(srv, arrivals, check):
    rid_of, step, remaining = {}, 0, 1
    while remaining or len(rid_of) < len(arrivals):
        for i, (at, prompt, new) in enumerate(arrivals):
            if at == step:
                rid_of[i] = srv.submit(prompt, max_new_tokens=new)
        remaining = srv.step()
        check(srv, step, rid_of)
        # the pending trip, and the chunk calls and uploads dispatched
        # since the call that was read last
        assert 0 <= srv.device_calls_in_flight() <= 1 + 2 * srv.max_batch
        srv.take_results()
        step += 1
    assert srv.step() == 0
    return rid_of


@pytest.mark.parametrize("telemetry", [None, True], ids=["off", "on"])
@pytest.mark.parametrize("name", NAMES)
def test_early_retires_keep_the_count_and_run_ends_at_zero(models, name,
                                                           telemetry):
    """A snapshot, a preemption with its swap-out and resume, a cancel, a
    read of the marks — each reads the pending trip early."""
    srv = _server(models[name], telemetry=telemetry)
    seen = []

    def meddle(srv, step, rid_of):
        if step == 4:
            srv.snapshot()
            assert srv.device_calls_in_flight() == 0
        if step == 6:
            assert srv._trips and srv._preempt_slot(1)
        if step == 12:
            assert srv.cancel(rid_of[2])
        if step == 14:
            srv.request_metrics()
            assert srv.device_calls_in_flight() == 0
        seen.append(srv.device_calls_in_flight())

    _drive(srv, LONG, meddle)
    assert srv.device_calls_in_flight() == 0
    assert srv._calls_seen == srv._calls_out > 0
    assert max(seen) >= 1
    assert _counter(srv, "serving_preemptions") == 1
    assert _counter(srv, "serving_resumes") == 1
    if telemetry:
        after = {s["args"]["after"] for s in _queue(srv)}
        # reads between steps name their reason
        assert {"snapshot", "preempt", "metrics"} <= after


def test_a_preemption_in_admit_keeps_the_count(models):
    srv = _server(models["llama"], policy="priority", telemetry=True)

    def arrive(srv, step, rid_of):
        if step == 5:
            srv.submit(_prompt(11, 9), max_new_tokens=6,
                       priority=PRIORITY_NORMAL - 1)

    _drive(srv, LONG, arrive)
    assert _counter(srv, "serving_preemptions") == 1
    assert srv.device_calls_in_flight() == 0
    # (inside a tick the read is named by its phase)
    assert any(s["args"]["after"] == "decode_wait" and s["name"] == "starved"
               for s in _queue(srv))


def test_pool_pressure_and_a_fault_keep_the_count(models):
    from paddle_tpu.inference.faults import FaultInjector, FaultPlan, FaultSpec

    srv = _server(models["llama"], num_blocks=14, telemetry=True,
                  faults=FaultInjector(FaultPlan(
                      [FaultSpec(site="tick", at=5, kind="transient")])))
    _drive(srv, LONG, lambda *a: None)
    assert _counter(srv, "serving_preemptions") >= 1
    assert _counter(srv, "serving_tick_retries") == 1
    assert srv.device_calls_in_flight() == 0


# ----------------------------------------------------- (e) telemetry off
@pytest.mark.parametrize("name", NAMES)
def test_telemetry_none_reads_no_clock_holds_no_span_same_tokens(models,
                                                                 name):
    def run(telemetry):
        clock = _Clock()
        srv = _server(models[name], clock, telemetry=telemetry)
        rids = [srv.submit(_prompt(n, i), max_new_tokens=7)
                for i, n in enumerate((21, 40, 9, 18))]
        reads0 = clock.calls
        out = srv.run()
        return srv, clock.calls - reads0, [out[r] for r in rids]

    off, reads, tokens = run(None)
    on, reads_on, tokens_on = run(True)
    assert tokens == tokens_on
    assert off._q_open is None and off.telemetry.tracer.spans() == []
    assert off.telemetry.registry.get(
        "serving_device_starved_seconds").series() == []
    assert off.telemetry.registry.get(
        "serving_device_no_work_seconds").series() == []
    # what the tick read before the device-queue row, and nothing else: two
    # reads around every chunk dispatch (the prefill-throughput ledger), one
    # first-token mark and one done mark a request
    chunks = int(_counter(off, "serving_prefill_chunks"))
    assert reads == 2 * chunks + 2 * 4
    assert reads_on > reads
    # the integers are kept either way
    assert off._calls_out == on._calls_out > 0
    assert off.device_calls_in_flight() == on.device_calls_in_flight() == 0


# ----------------------------------------- (f) the spans and the counters
@pytest.mark.parametrize("name", NAMES)
def test_the_sum_of_the_spans_equals_the_two_counters(models, name):
    clock = _Clock()
    srv = _server(models[name], clock, telemetry=True)
    arrivals = LONG + [(9, _prompt(13, 4), 12), (30, _prompt(40, 5), 9)]
    _drive(srv, arrivals, lambda *a: None)
    for _ in range(3):
        srv.step()
    srv.submit(_prompt(9, 6), max_new_tokens=4)
    srv.run()
    q = _queue(srv)
    assert {s["name"] for s in q} == set(DEVICE_QUEUE_SPANS)
    by_after = {}
    for s in q:
        if s["name"] == "starved":
            a = s["args"]["after"]
            by_after[a] = by_after.get(a, 0.0) + s["dur"]
    assert {"first_token_wait", "arrival"} <= set(by_after)
    for a, sec in by_after.items():
        assert _counter(srv, "serving_device_starved_seconds",
                        after=a) == pytest.approx(sec)
    assert _counter(srv, "serving_device_starved_seconds") == pytest.approx(
        sum(by_after.values()))
    assert _counter(srv, "serving_device_no_work_seconds") == pytest.approx(
        sum(s["dur"] for s in q if s["name"] == "no_work"))
    # one row, one span open at a time
    for a, b in zip(q, q[1:]):
        assert a["t1"] <= b["t0"]
    assert srv.telemetry.tracer.dropped == 0
    assert "serving_device_starved_seconds" in \
        srv.telemetry.registry.to_prometheus()


# ------------------------------------------------- (g) the pt.* twins
class _Recorder:
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _Recorder.log.append(("enter", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name, self.kw))
        return False


def test_every_span_has_a_pt_annotation_that_crosses_phases(models,
                                                            monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    srv = _server(models["llama"], telemetry=True)
    srv.submit(_prompt(9, 1), max_new_tokens=12)
    for _ in range(3):
        srv.step()
    srv.submit(_prompt(11, 2), max_new_tokens=4)
    srv.run()
    names = {"pt." + n for n in DEVICE_QUEUE_SPANS}
    log = [e for e in _Recorder.log if e[1] in names]
    # begun and ended in turn, never two open at once
    assert [e[0] for e in log[:2 * (len(log) // 2)]] == \
        ["enter", "exit"] * (len(log) // 2)
    ended = [(n, kw) for kind, n, kw in log if kind == "exit"]
    assert [(n[3:], kw["after"], kw["tick"]) for n, kw in ended] == [
        (s["name"], s["args"]["after"], s["args"]["tick"])
        for s in _queue(srv)]
    # the one that is still open: the drained server's
    assert log[-1][:2] == ("enter", "pt.no_work")
    # a rider's first-token sync runs from one tick into the next
    full = _Recorder.log
    i = max(k for k, e in enumerate(full)
            if e[:2] == ("enter", "pt.starved")
            and e[2]["after"] == "first_token_wait")
    j = next(k for k in range(i, len(full)) if full[k][:2] == ("exit",
                                                              "pt.starved"))
    between = [e[1] for e in full[i:j] if e[0] == "enter"]
    assert "pt.tick" in between and "pt.decode_dispatch" in between


# ------------------------------------------- (h) servers that cannot count
def test_a_host_side_drafter_declares_the_count_off(models):
    from paddle_tpu.inference.speculative import SpecConfig

    llama = models["llama"]
    srv = _server(llama, telemetry=True,
                  spec=SpecConfig(k=2, drafter="model", draft_model=llama))
    rid = srv.submit(_prompt(9, 1), max_new_tokens=8)
    out = srv.run()
    assert len(out[rid]) == 9 + 8
    assert srv.device_calls_in_flight() is None
    assert _queue(srv) == [] and srv._q_open is None
    assert _counter(srv, "serving_device_starved_seconds") == 0
    run = _as_run(srv)
    assert queue_readers.device_starved(run) is None
    assert queue_readers.no_work(run) is None
    assert queue_readers.starved_after_first_token(run) is None


def test_an_in_program_drafter_keeps_it(models):
    from paddle_tpu.inference.speculative import SpecConfig

    srv = _server(models["llama"], telemetry=True,
                  spec=SpecConfig(k=2, drafter="ngram", gate_ticks=4,
                                  gate_cooldown=2, gate_low=1.5))
    for _, p, new in LONG:
        srv.submit(p, max_new_tokens=new)
    srv.run()
    assert srv.device_calls_in_flight() == 0
    progs = {s["args"].get("prog") for s in _queue(srv)}
    assert "spec_scan" in progs
    # every trip is read at once: each read leaves the queue empty
    assert len([s for s in _queue(srv)
                if s["args"]["after"] == "decode_wait"]) >= srv._step_no - 1


# --------------------------------- (i) the benchmark's readers, held here
def _as_run(srv):
    """The tracer's spans as ``drivers/serve_paged.py`` keeps them, with a
    window that holds the whole run."""
    spans = [{"rid": s["rid"], "name": s["name"], "t0": s["t0"],
              "dur": s["dur"]} for s in srv.telemetry.tracer.spans()]
    t0 = min(s["t0"] for s in spans)
    firsts = [s["t0"] for s in spans if s["name"] == "first_token"]
    return {"spans": spans, "seconds": max(s["t0"] + s["dur"]
                                           for s in spans) - t0 + 1.0,
            "requests": [{"first_token_t": t - t0} for t in firsts]}


def test_the_names_declared_are_the_names_the_benchmark_reads():
    assert tuple(queue_readers.NAMES) == tuple(DEVICE_QUEUE_SPANS)
    assert queue_readers.DEVICE_QUEUE_RID == DEVICE_QUEUE_RID
    assert queue_readers.declared() == (DEVICE_QUEUE_RID, DEVICE_QUEUE_SPANS)
    assert queue_readers.FIRST_TOKEN_WAIT in T._WAIT_PHASES
    assert len({T.TRAIN_RID, ENGINE_RID, DEVICE_QUEUE_RID}) == 3
    assert T._ROW_NAMES[DEVICE_QUEUE_RID] == "device queue"


@pytest.mark.parametrize("name", NAMES)
def test_the_readers_agree_with_the_counters_on_a_whole_run(models, name):
    clock = _Clock()
    srv = _server(models[name], clock, telemetry=True)
    arrivals = LONG + [(7, _prompt(13, 4), 12), (11, _prompt(40, 5), 9),
                       (12, _prompt(30, 6), 9)]
    _drive(srv, arrivals, lambda *a: None)
    srv.submit(_prompt(9, 7), max_new_tokens=3)
    srv.run()
    run = _as_run(srv)
    sec = run["seconds"]
    starved = _counter(srv, "serving_device_starved_seconds")
    assert queue_readers.device_starved(run) == pytest.approx(
        100 * starved / sec)
    assert queue_readers.starved_after_first_token(run) == pytest.approx(
        100 * _counter(srv, "serving_device_starved_seconds",
                       after="first_token_wait") / sec)
    assert queue_readers.no_work(run) == pytest.approx(
        100 * _counter(srv, "serving_device_no_work_seconds") / sec)
    fused = _counter(srv, "serving_prefill_chunks_fused")
    alone = _counter(srv, "serving_prefill_chunks_alone")
    assert fused + alone == _counter(srv, "serving_prefill_chunks") > 0
    assert queue_readers.fused_chunk_share(run) == pytest.approx(
        100 * fused / (fused + alone))
    assert (fused > 0) == (srv._exec.chunk_alone_why is None)


def test_the_chrome_trace_names_the_row(models, tmp_path):
    import json

    srv = _server(models["llama"], telemetry=True)
    srv.submit(_prompt(9, 1), max_new_tokens=3)
    srv.run()
    with open(srv.export_chrome_trace(str(tmp_path / "t.json"))) as f:
        events = json.load(f)["traceEvents"]
    rows = {e["tid"]: e["args"]["name"] for e in events
            if e["name"] == "thread_name"}
    assert rows[DEVICE_QUEUE_RID] == "device queue"
    assert {e["name"] for e in events if e.get("tid") == DEVICE_QUEUE_RID
            and e["ph"] == "X"} <= set(DEVICE_QUEUE_SPANS)


def test_a_queue_span_of_the_facade_alone():
    clock = _Clock()
    tel = ServingTelemetry(enabled=True, clock=clock)
    q = tel.queue_span(0.5, "decode_wait", 7)
    assert q.name is None and clock.calls == 0
    q.settle("no_work")
    q.settle("starved")                       # settled once
    t1 = q.close(prog="decode_paged")
    (s,) = tel.tracer.spans(DEVICE_QUEUE_RID)
    assert (s["name"], s["t0"], s["t1"]) == ("no_work", 0.5, t1)
    assert s["args"] == {"after": "decode_wait", "tick": 7,
                         "prog": "decode_paged"}
    # never settled: a dispatch came first, so there was work
    q = tel.queue_span(1.0, "first_token_wait", 8)
    q.close()
    assert tel.tracer.spans(DEVICE_QUEUE_RID)[-1]["name"] == "starved"


# --------------------------------------- (j) tools/profile_step.py's lines
def _planes(queue):
    """The device of tests/test_profile_step_tool.py: ops 0.010-0.060,
    0.064-0.070, 0.072-0.090, 0.091-0.120, 0.200-0.210; idle 0.004 + 0.002 +
    0.001 + 0.080 = 0.087 s."""
    ops = [("%fusion.1 = bf16[8,8] fusion(", 0.010, 0.060),
           ("%fusion.2 = bf16[8,8] fusion(", 0.064, 0.070),
           ("%custom-call.3 = bf16[4,8] custom-call(", 0.072, 0.090),
           ("%custom-call.4 = bf16[4,8] custom-call(", 0.091, 0.120),
           ("%fusion.5 = bf16[8,8] fusion(", 0.200, 0.210)]
    mods = [("jit_decode(1)", 0.010, 0.060), ("jit_chunk(2)", 0.064, 0.070),
            ("jit_decode(1)", 0.072, 0.120), ("jit_decode(1)", 0.200, 0.210)]
    host = [("pt.tick", 0.000, 0.063), ("pt.decode_wait", 0.012, 0.061),
            ("pt.tick", 0.0632, 0.125), ("pt.prefill", 0.0633, 0.071),
            ("pt.first_token_wait", 0.0650, 0.0705),
            ("pt.decode_dispatch", 0.0711, 0.073),
            ("pt.decode_wait", 0.073, 0.121)] + queue
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
            "/host:CPU": {"python3": host}}


def test_soundness_and_coverage_over_the_devices_gaps():
    # the first-token sync: known empty from the wait's end, 0.5 ms after
    # the chunk program ended, to 0.5 ms after the next program began; the
    # idle server from the read of the last trip until a dispatch whose
    # program starts 2 ms before the call returns; one before the first op
    queue = [("pt.starved", 0.0705, 0.0725), ("pt.no_work", 0.121, 0.202),
             ("pt.no_work", 0.001, 0.011)]
    lines = P.report(_planes(queue), top=3, host_prefix="")
    text = "\n".join(lines)
    # inside idle: 0.0705-0.072 and 0.121-0.200; called empty: 0.002 + 0.081
    # + the 1 ms of the early one that lies after the first op
    assert "pt.starved: 1 spans, 0.0020 s, of which 0.0015 s" in text
    assert "pt.no_work: 2 spans, 0.0820 s, of which 0.0790 s" in text
    sound = next(ln for ln in lines if "soundness" in ln)
    cover = next(ln for ln in lines if "coverage" in ln)
    assert f"{100 * 0.0805 / 0.084:.1f} %" in sound
    assert f"{100 * 0.0805 / 0.087:.1f} %" in cover
    # the queue row's annotations cross phases: the charging leaves them out
    at = next(i for i, ln in enumerate(lines)
              if ln.startswith("idle time by host phase"))
    phases = [ln.split(None, 4)[-1] for ln in lines[at + 3:
                                                    lines.index("", at)]]
    assert not set(phases) & set(P.QUEUE)
    assert set(P.QUEUE) == {"pt." + n for n in DEVICE_QUEUE_SPANS}


def test_a_device_plane_that_runs_early_is_said_and_undone():
    """Both spans end before the host dispatches, yet the next program is
    on the device plane 1.5 and 2 ms before their ends: the planes disagree
    by at least 1.5 ms, and moved by that the spans claim less busy time."""
    queue = [("pt.starved", 0.0705, 0.0735), ("pt.no_work", 0.121, 0.202)]
    lines = P.report(_planes(queue), top=3, host_prefix="")
    check = [ln for ln in lines if "clock check" in ln]
    assert len(check) == 1
    assert "in 2 of 2 spans" in check[0] and "1.500-2.000 ms" in check[0]
    assert "at least 1.500 ms early" in check[0]
    # as is: 0.0015 + 0.079 idle of 0.003 + 0.081 called empty; with every
    # gap 1.5 ms later the device is idle 0.0715-0.0735 and 0.1215-0.2015
    assert f"soundness {100 * 0.0805 / 0.084:.1f} %" in \
        next(ln for ln in lines if ln.lstrip().startswith("soundness"))
    assert f"soundness {100 * (0.002 + 0.080) / 0.084:.1f} %" in check[0]
    assert f"coverage {100 * (0.002 + 0.080) / 0.087:.1f} %" in check[0]
    assert P.plane_skew([("pt.starved", 0.0, 1.0)],
                        [("m", 0.5, 0.9), ("m", 0.95, 1.5)]) == \
        [pytest.approx(0.05)]


def test_a_trace_without_the_row_says_so():
    lines = P.report(_planes([]), top=3, host_prefix="")
    assert any("no pt.starved / pt.no_work annotation" in ln for ln in lines)
    assert not any("soundness" in ln for ln in lines)
    assert P.overlap([(0.0, 1.0), (2.0, 3.0)], [(0.5, 2.5)]) == \
        pytest.approx(1.0)
