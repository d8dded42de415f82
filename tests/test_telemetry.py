"""Serving telemetry (inference/telemetry.py + GenerationServer wiring):
registry percentiles vs numpy, Prometheus exposition, flight-ring
wraparound, watchdog findings, the allocation-free disabled path, and —
on a real CPU server — span-tree well-formedness across preempt/swap/
resume and cancel-mid-spec-window. Quick tier on CPU."""
import json
import tracemalloc

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.telemetry import (NULL_FLIGHT, NULL_TRACER,
                                            FlightRecorder, Histogram,
                                            MetricsRegistry, ServingTelemetry,
                                            SpanTracer, watchdog)


class _FakeClock:
    """Deterministic injectable clock: each call returns the next value."""

    def __init__(self, step=1.0, start=0.0):
        self.t = start
        self.step = step

    def __call__(self):
        v = self.t
        self.t += self.step
        return v


# --------------------------------------------------------------------------
# MetricsRegistry
# --------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_histogram_percentiles_match_numpy(self):
        reg = MetricsRegistry()
        rng = np.random.RandomState(3)
        xs = rng.exponential(0.05, 500)
        h = reg.histogram("lat_s", "latency")
        for x in xs:
            h.observe(float(x))
        for q in (50, 90, 95, 99):
            assert reg.percentile("lat_s", q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)

    def test_labeled_percentiles_and_where_filter(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft_s")
        a = [0.01, 0.02, 0.03]
        b = [0.5, 0.6]
        for x in a:
            h.observe(x, tenant="a", priority=0)
        for x in b:
            h.observe(x, tenant="b", priority=1)
        assert reg.percentile("ttft_s", 50, where={"tenant": "a"}) == \
            pytest.approx(np.percentile(a, 50))
        # int label values match their str coercion (priority=0 vs "0")
        assert reg.percentile("ttft_s", 50, where={"priority": 1}) == \
            pytest.approx(np.percentile(b, 50))
        assert reg.percentile("ttft_s", 50) == \
            pytest.approx(np.percentile(a + b, 50))
        assert h.count({"tenant": "b"}) == 2
        assert h.label_values("tenant") == ["a", "b"]

    def test_clipped_series_falls_back_to_buckets(self):
        h = Histogram("h", buckets=(0.1, 0.2, 0.4), max_samples=4)
        for _ in range(50):
            h.observe(0.15)
        p = h.percentile(50)
        assert 0.1 <= p <= 0.2          # interpolated inside its bucket
        assert h.count() == 50          # bucket counts never clip

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("n")
        with pytest.raises(TypeError):
            reg.gauge("n")
        with pytest.raises(TypeError):
            reg.histogram("n")

    def test_counter_gauge_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("req", "requests")
        c.inc(tenant="a")
        c.inc(2, tenant="b")
        assert c.value(tenant="a") == 1 and c.total() == 3
        assert c.total(where={"tenant": "b"}) == 2
        g = reg.gauge("depth")
        g.set(5)
        g.set(2)
        assert g.value() == 2

    def test_timer_uses_injected_clock(self):
        clk = _FakeClock(step=0.25)
        reg = MetricsRegistry(clock=clk)
        with reg.timer("block_s", phase="x"):
            pass
        assert reg.get("block_s").samples() == [0.25]

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests served").inc(3, tenant="a")
        h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.to_prometheus()
        assert "# HELP req_total requests served" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{tenant="a"} 3.0' in text
        assert "# TYPE lat_s histogram" in text
        # cumulative le buckets + the +Inf catch-all
        assert 'lat_s_bucket{le="0.1"} 1' in text
        assert 'lat_s_bucket{le="1.0"} 2' in text
        assert 'lat_s_bucket{le="+Inf"} 3' in text
        assert "lat_s_count 3" in text
        assert "lat_s_sum 5.55" in text

    def test_to_json_carries_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_s")
        for x in (0.1, 0.2, 0.3, 0.4):
            h.observe(x, tenant="a")
        j = reg.to_json()
        e = j["histograms"]["lat_s"]
        assert e["count"] == 4
        assert e["p50"] == pytest.approx(np.percentile([0.1, 0.2, 0.3, 0.4],
                                                       50))
        assert e["series"][0]["labels"] == {"tenant": "a"}

    def test_reset_histograms_keeps_counters(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(7)
        reg.histogram("h").observe(1.0)
        reg.reset_histograms()
        assert reg.counter("c").total() == 7
        assert reg.histogram("h").count() == 0


# --------------------------------------------------------------------------
# SpanTracer
# --------------------------------------------------------------------------

class TestSpanTracer:
    def test_begin_end_deterministic_clock(self):
        tr = SpanTracer(clock=_FakeClock())
        tr.begin(1, "queued")            # t0 = 0
        tr.end(1, "queued")              # t1 = 1
        (s,) = tr.spans(1)
        assert (s["t0"], s["t1"], s["dur"]) == (0.0, 1.0, 1.0)

    def test_complete_is_retroactive(self):
        tr = SpanTracer(clock=_FakeClock())
        tr.complete(2, "spec_window", 10.0, 12.5, ticks=4)
        (s,) = tr.spans(2)
        assert s["dur"] == 2.5 and s["args"]["ticks"] == 4

    def test_close_ends_all_open_and_marks_outcome(self):
        tr = SpanTracer(clock=_FakeClock())
        tr.begin(3, "prefill")
        tr.begin(3, "preempted")
        tr.close(3, "cancelled")
        assert tr.open_spans(3) == []
        names = [s["name"] for s in tr.spans(3)]
        assert names.count("cancelled") == 1           # outcome instant
        assert {"prefill", "preempted"} <= set(names)
        for s in tr.spans(3):
            if s["name"] in ("prefill", "preempted"):
                assert s["args"]["outcome"] == "cancelled"

    def test_rebegin_closes_previous(self):
        tr = SpanTracer(clock=_FakeClock())
        tr.begin(4, "queued")
        tr.begin(4, "queued")            # implicit end of the first
        assert len(tr.spans(4)) == 1 and tr.open_spans(4) == ["queued"]

    def test_max_spans_drops_and_counts(self):
        tr = SpanTracer(clock=_FakeClock(), max_spans=2)
        for i in range(4):
            tr.complete(1, f"s{i}", 0.0, 1.0)
        assert len(tr.spans()) == 2 and tr.dropped == 2

    def test_chrome_events_one_row_per_request(self):
        tr = SpanTracer(clock=_FakeClock())
        tr.set_meta(7, tenant="acme")
        tr.complete(7, "spec_window", 0.0, 1.0)
        tr.instant(7, "first_token")
        evs = tr.chrome_events()
        meta = [e for e in evs if e["ph"] == "M" and
                e["name"] == "thread_name"]
        assert meta[0]["tid"] == 7 and "acme" in meta[0]["args"]["name"]
        assert {e["tid"] for e in evs} == {7}
        x = next(e for e in evs if e["ph"] == "X")
        assert x["ts"] == 0.0 and x["dur"] == 1e6      # microseconds

    def test_forwards_to_profiler_recorder(self):
        from paddle_tpu import profiler

        rec = profiler._recorder
        tr = SpanTracer(clock=_FakeClock())
        rec.drain()
        rec.enabled = True
        try:
            tr.complete(9, "swap_out", 1.0, 2.0, blocks=3)
        finally:
            rec.enabled = False
        (ev,) = rec.drain()
        assert ev["name"] == "serving::swap_out"
        assert ev["tid"] == 1_000_000 + 9 and ev["cat"] == "serving"
        assert ev["args"]["blocks"] == 3


# --------------------------------------------------------------------------
# FlightRecorder + watchdog
# --------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_wraparound_oldest_to_newest(self):
        fr = FlightRecorder(size=4)
        for i in range(10):
            fr.record(tick=i)
        assert fr.total == 10 and len(fr) == 4
        dump = fr.dump()
        assert [r["tick"] for r in dump] == [6, 7, 8, 9]
        assert [r["seq"] for r in dump] == [6, 7, 8, 9]

    def test_underfull_ring(self):
        fr = FlightRecorder(size=8)
        fr.record(a=1)
        fr.record(a=2)
        assert [r["a"] for r in fr.dump()] == [1, 2]

    def test_reset(self):
        fr = FlightRecorder(size=4)
        fr.record(x=1)
        fr.reset()
        assert fr.dump() == [] and fr.total == 0


def _ticks(n, **base):
    return [dict(base, seq=i, prog="decode", preemptions=0, stalls=0,
                 recompiles=0) for i in range(n)]


class TestWatchdog:
    def test_quiet_run_no_findings(self):
        assert watchdog(_ticks(64)) == []

    def test_preemption_storm(self):
        recs = _ticks(64)
        for i in range(20, 30):
            recs[i]["preemptions"] = 1
        (f,) = watchdog(recs)
        assert f["kind"] == "preemption_storm" and f["count"] >= 8

    def test_pool_pressure_stall(self):
        recs = _ticks(64)
        for i in range(16, 48):
            recs[i]["stalls"] = 2
        kinds = [f["kind"] for f in watchdog(recs)]
        assert "pool_pressure_stall" in kinds

    def test_steady_state_recompile_flagged(self):
        recs = _ticks(64)
        recs[40]["recompiles"] = 1       # "decode" seen on every prior tick
        (f,) = watchdog(recs)
        assert f["kind"] == "steady_state_recompile" and f["seq"] == 40

    def test_first_seen_program_excused(self):
        recs = _ticks(64)
        recs[40]["prog"] = "spec:w4"     # gate flip: new program, compiles
        recs[40]["recompiles"] = 1
        assert watchdog(recs) == []

    def test_warmup_ticks_excused(self):
        recs = _ticks(64)
        recs[3]["recompiles"] = 2        # inside warmup_ticks=8
        assert watchdog(recs) == []


# --------------------------------------------------------------------------
# Disabled path
# --------------------------------------------------------------------------

class TestDisabledPath:
    def test_null_singletons_installed(self):
        tel = ServingTelemetry(enabled=False)
        assert tel.tracer is NULL_TRACER and tel.flight is NULL_FLIGHT
        assert tel.registry is not None  # registry is ALWAYS real
        tel.tracer.begin(1, "x")
        tel.flight.record(tick=1)
        assert tel.tracer.spans() == [] and tel.flight.dump() == []
        assert tel.snapshot()["flight_ticks"] == 0

    def test_disabled_calls_do_not_accumulate_memory(self):
        """The overhead contract: the no-op tracer/flight retain NOTHING —
        traced memory growth over 20k disabled-path calls stays bounded
        (O(1), not O(calls))."""
        tel = ServingTelemetry(enabled=False)
        tr, fl = tel.tracer, tel.flight
        for i in range(100):             # warm any lazy caches
            tr.begin(i, "s")
            fl.record(t=i)
        tracemalloc.start()
        before = tracemalloc.get_traced_memory()[0]
        for i in range(20_000):
            tr.begin(i, "s", a=1)
            tr.end(i, "s")
            tr.complete(i, "w", 0.0, 1.0, ticks=4)
            fl.record(t_wall_s=0.1, prog="decode", preemptions=0)
        grown = tracemalloc.get_traced_memory()[0] - before
        tracemalloc.stop()
        assert grown < 64 * 1024, f"disabled path retained {grown} bytes"


# --------------------------------------------------------------------------
# GenerationServer integration (CPU)
# --------------------------------------------------------------------------

def _model(max_pos=160):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=max_pos,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


def _prompts(cfg, lens):
    rng = np.random.RandomState(11)
    return [rng.randint(1, cfg.vocab_size, (n,)).tolist() for n in lens]


def test_preempt_swap_resume_spans_share_one_timeline(tmp_path):
    """The acceptance trace: a request preempted mid-decode must show
    queued → prefill → decode → swap_out → preempted → swap_in →
    decode → complete, all on ONE chrome-trace row (tid = rid),
    with no span left open — and the sched_metrics() dict must be a view
    of the same registry counters."""
    from paddle_tpu.inference.serving import GenerationServer

    model, cfg = _model()
    prompts = _prompts(cfg, (21, 33, 18, 27))
    # 6 usable blocks << demand -> decode-phase preemption (swap to host)
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, num_blocks=7,
                           policy="priority", telemetry=True)
    rids = [srv.submit(p, max_new_tokens=12, priority=i % 2)
            for i, p in enumerate(prompts)]
    out = srv.run()
    assert sorted(out) == sorted(rids)
    sm = srv.sched_metrics()
    assert sm["preemptions"] >= 1 and sm["resumes"] >= 1

    tr = srv.telemetry.tracer
    reg = srv.telemetry.registry
    for r in rids:
        assert tr.open_spans(r) == [], f"rid {r} left spans open"
        names = [s["name"] for s in tr.spans(r)]
        assert names[0] == "queued" and names[-1] == "complete"
        assert "first_token" in names and "decode" in names
        # O(1) spans a request: one bracket per stay in a slot, not one
        # span per tick
        assert "decode_window" not in names
        assert names.count("decode") == 1 + names.count("preempted")
    victim = next(r for r in rids
                  if "swap_out" in [s["name"] for s in tr.spans(r)])
    vnames = [s["name"] for s in tr.spans(victim)]
    for needed in ("swap_out", "preempted", "swap_in"):
        assert needed in vnames
    assert vnames.index("swap_out") < vnames.index("swap_in")
    # swap spans carry the block/byte payloads the offload engine observed
    sw = next(s for s in tr.spans(victim) if s["name"] == "swap_out")
    assert sw["args"]["blocks"] >= 1 and sw["args"]["bytes"] > 0
    assert reg.histogram("serving_swap_out_s").count() >= 1
    assert reg.counter("serving_swap_out_bytes").total() > 0

    # registry counters ARE the sched_metrics values
    assert sm["preemptions"] == reg.counter("serving_preemptions").total()
    assert sm["resumes"] == reg.counter("serving_resumes").total()
    assert sm["submitted"] == \
        reg.counter("sched_requests_submitted").total() == len(rids)

    # one timeline row per request in the exported chrome trace
    path = srv.export_chrome_trace(str(tmp_path / "trace.json"))
    evs = json.load(open(path))["traceEvents"]
    victim_evs = [e for e in evs if e.get("tid") == victim
                  and e["ph"] in ("X", "i")]
    vnames_tr = {e["name"] for e in victim_evs}
    assert {"swap_out", "swap_in", "decode"} <= vnames_tr
    assert vnames.count("decode") >= 2          # before and after the swap
    assert {e["tid"] for e in victim_evs} == {victim}

    # the flight ring saw the preemption ticks + per-tick pool state
    ticks = srv.telemetry.flight.dump()
    assert ticks and sum(t["preemptions"] for t in ticks) >= 1
    assert all("blocks_in_use" in t and "prog" in t for t in ticks)


def test_cancel_mid_spec_window_closes_spans():
    """Cancelling a request mid-speculative-window must leave a
    well-formed span tree (everything closed, a 'cancelled' outcome
    marker) and count the drop under reason=cancelled."""
    from paddle_tpu.inference.serving import GenerationServer
    from paddle_tpu.inference.speculative import SpecConfig

    model, cfg = _model()
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=4, prefill_chunk=8,
                           spec=SpecConfig(k=4, gate_cooldown=0),
                           telemetry=True)
    rid = srv.submit(_prompts(cfg, (10,))[0], max_new_tokens=40)
    keep = srv.submit(_prompts(cfg, (6,))[0], max_new_tokens=8)
    for _ in range(4):                   # prefill + spec windows ran
        srv.step()
    assert srv.status(rid) == "running"
    assert srv.cancel(rid) is True
    out = srv.run()
    assert rid not in out and keep in out

    tr = srv.telemetry.tracer
    assert tr.open_spans(rid) == []
    names = [s["name"] for s in tr.spans(rid)]
    assert "spec_window" in names and "cancelled" in names
    reg = srv.telemetry.registry
    assert reg.counter("serving_requests_dropped") \
        .value(reason="cancelled") == 1
    assert srv.sched_metrics()["cancelled"] == 1
    # the survivor closed normally
    assert [s["name"] for s in tr.spans(keep)][-1] == "complete"
    # spec windows recorded acceptance in the flight ring
    ticks = srv.telemetry.flight.dump()
    assert any(t.get("spec_proposed", 0) > 0 for t in ticks)


def test_registry_reproduces_request_metrics_percentiles():
    """The benchmark contract: TTFT/TPOT percentiles from the registry
    histograms must equal numpy percentiles over the ad-hoc per-request
    marks (request_metrics) — two views of the same samples."""
    from paddle_tpu.inference.serving import GenerationServer

    model, cfg = _model()
    prompts = _prompts(cfg, (9, 17, 12, 30, 7, 22))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, telemetry=True)
    rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    srv.run()
    rm = srv.request_metrics()
    ttft = [rm[r]["first_token_t"] - rm[r]["submit_t"] for r in rids]
    tpot = [1e3 * (rm[r]["done_t"] - rm[r]["first_token_t"])
            / (rm[r]["n_generated"] - 1)
            for r in rids if rm[r].get("n_generated", 0) > 1]
    reg = srv.telemetry.registry
    for q in (50, 95):
        assert reg.percentile("serving_ttft_s", q) == pytest.approx(
            float(np.percentile(ttft, q)), rel=1e-9)
        assert reg.percentile("serving_tpot_ms", q) == pytest.approx(
            float(np.percentile(tpot, q)), rel=1e-9)
    # per-tenant breakdown is the same registry data
    tb = srv.sched_metrics()["tenants"]["default"]
    assert tb["completed"] == len(rids)
    assert tb["ttft_p50_ms"] == pytest.approx(
        float(np.percentile(ttft, 50)) * 1e3, rel=1e-9)
    # the snapshot blob is JSON-serializable end to end
    json.dumps(srv.telemetry_snapshot())


def test_disabled_server_records_nothing_but_counts():
    """telemetry=None (the default): no spans, no flight ticks — but the
    registry counters behind sched_metrics() still work."""
    from paddle_tpu.inference.serving import GenerationServer

    model, cfg = _model()
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16)
    rids = [srv.submit(p, max_new_tokens=6)
            for p in _prompts(cfg, (9, 14))]
    srv.run()
    assert srv.telemetry.enabled is False
    assert srv.telemetry.tracer is NULL_TRACER
    assert srv.telemetry.flight.total == 0
    assert srv.sched_metrics()["submitted"] == len(rids)
    # TTFT histograms still feed the benchmark percentiles when disabled
    assert srv.telemetry.registry.percentile("serving_ttft_s", 50) \
        is not None


# --------------------------------------------------------------------------
# Registry edge cases (exposition hardening)
# --------------------------------------------------------------------------

class TestMetricsEdgeCases:
    def test_empty_histogram_percentile_is_none(self):
        reg = MetricsRegistry()
        reg.histogram("lat_s")               # registered, zero observations
        assert reg.percentile("lat_s", 50) is None
        assert reg.percentile("never_registered", 95) is None
        # labeled miss on a histogram that HAS other-label data
        reg.histogram("lat_s").observe(0.2, tenant="a")
        assert reg.percentile("lat_s", 50, where={"tenant": "ghost"}) is None

    def test_bucket_boundary_value_counts_in_its_le_bucket(self):
        # Prometheus le buckets are INCLUSIVE upper bounds: an observation
        # exactly on an edge belongs to that edge's bucket (searchsorted
        # side="left"), not the next one up
        reg = MetricsRegistry()
        h = reg.histogram("lat_s", buckets=(0.1, 1.0))
        h.observe(0.1)                       # exactly the first edge
        h.observe(1.0)                       # exactly the last finite edge
        h.observe(0.1 + 1e-9)                # just past the edge
        text = reg.to_prometheus()
        assert 'lat_s_bucket{le="0.1"} 1' in text
        assert 'lat_s_bucket{le="1.0"} 3' in text
        assert 'lat_s_bucket{le="+Inf"} 3' in text

    def test_prometheus_escapes_hostile_tenant_names(self):
        # scrape-format hardening: a tenant string is attacker-ish input;
        # quotes/backslashes/newlines must come out escaped, one line per
        # series, instead of corrupting the exposition
        reg = MetricsRegistry()
        c = reg.counter("req_total")
        c.inc(tenant='evil"name')
        c.inc(tenant="back\\slash")
        c.inc(tenant="two\nlines")
        text = reg.to_prometheus()
        assert 'req_total{tenant="evil\\"name"} 1.0' in text
        assert 'req_total{tenant="back\\\\slash"} 1.0' in text
        assert 'req_total{tenant="two\\nlines"} 1.0' in text
        # every series stayed on one physical line
        assert sum(1 for ln in text.splitlines()
                   if ln.startswith("req_total{")) == 3


# --------------------------------------------------------------------------
# Warm-program fold across the warmup-boundary reset
# --------------------------------------------------------------------------

class TestWarmProgramFold:
    def test_reset_fold_warm_carries_prog_keys(self):
        fr = FlightRecorder(size=8)
        fr.record(prog="decode")
        fr.record(prog="prefill:16")
        fr.record(prog=None)                 # progless tick folds nothing
        fr.reset(fold_warm=True)
        assert fr.dump() == [] and fr.total == 0
        assert fr.warm_progs == {"decode", "prefill:16"}
        # a second boundary ACCUMULATES (warmup then measured-region reset)
        fr.record(prog="spec:w4")
        fr.reset(fold_warm=True)
        assert fr.warm_progs == {"decode", "prefill:16", "spec:w4"}

    def test_plain_reset_does_not_fold(self):
        fr = FlightRecorder(size=4)
        fr.record(prog="decode")
        fr.reset()
        assert fr.warm_progs == set()

    def test_warm_prog_recompile_flagged_inside_warmup_window(self):
        # "decode" compiled before the boundary; a post-boundary compile of
        # it is a finding even at measured tick 0 — the warmup_ticks
        # excusal must not mask it
        recs = _ticks(6)
        recs[0]["recompiles"] = 1
        (f,) = watchdog(recs, warm_progs={"decode"})
        assert f["kind"] == "steady_state_recompile" and f["seq"] == 0

    def test_new_program_still_excused_with_warm_set(self):
        # warm_progs must not revoke the first-appearance excusal for a
        # genuinely new program key
        recs = _ticks(64)
        recs[40]["prog"] = "spec:w4"
        recs[40]["recompiles"] = 1
        assert watchdog(recs, warm_progs={"decode"}) == []

    def test_serving_reset_folds_and_watchdog_uses_it(self):
        tel = ServingTelemetry()
        tel.flight.record(prog="decode", recompiles=1,
                          preemptions=0, stalls=0)
        tel.reset()                          # the warmup boundary
        assert "decode" in tel.flight.warm_progs
        tel.flight.record(prog="decode", recompiles=1,
                          preemptions=0, stalls=0)
        kinds = [f["kind"] for f in tel.watchdog()]
        assert kinds == ["steady_state_recompile"]
