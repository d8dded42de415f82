"""The readers of the program's device-queue row (``queue_readers.py``): on a
hand-made run (known answers, clipping at the window's edges; None without
the capability, 0.0 with it and nothing in the window, an error for a renamed
span), on the CPU rehearsal of a fixture cell through ``run_cell``, against
the program's own counters on one run, and the repo's manifest entries."""
import importlib
import os
import shutil
import tempfile

import pytest

from conftest import FIXTURES, ROOT

from benchmarks import queue_readers as Q
from benchmarks import run as R
from benchmarks import span_readers as S

QUEUE_FIXTURES = os.path.join(FIXTURES, "queue")
BASE = 7000.0                  # where the span clock stands at window start
CAPACITY = ["mistral7b-serve-decode", "phi4flash-serve-long-decode",
            "mistralsmall4-serve-long-decode", "granite4h-serve-chat-decode"]
NEW = {
    "device_starved.decode": ("engine", "serve_tok_s", CAPACITY),
    "device_starved.prefill": ("engine", "ttft_p95_ms",
                               ["mistral7b-serve-prefill"]),
    "starved_after_first_token.decode": ("engine", "serve_tok_s", CAPACITY),
    "no_work.prefill": ("scheduler", "ttft_p95_ms",
                        ["mistral7b-serve-prefill"]),
    "fused_chunk_share.decode": ("scheduler", "serve_tok_s",
                                 [CAPACITY[0], CAPACITY[2], CAPACITY[3]]),
}
QUEUE_METRICS = [n for n in NEW if n != "fused_chunk_share.decode"]


def _e(name, t0, dur):
    return {"rid": S.ENGINE_RID, "name": name, "t0": BASE + t0, "dur": dur}


def _q(name, t0, dur):
    return {"rid": Q.DEVICE_QUEUE_RID, "name": name, "t0": BASE + t0,
            "dur": dur}


def handmade():
    """A window of 2 s. Engine row: a first-token wait that ends at 0.500
    and one at 1.000, a decode wait that ends at 0.900, dispatches at
    0.100-0.110 and 0.600-0.610. Device-queue row: ``starved`` -0.010..0.030
    (straddles the start: 30 ms count), 0.500..0.520 (begins where the
    first-token wait ends), 0.900..0.910 (after a decode wait), 2.200..2.300
    (after the window); ``no_work`` 1.200..1.500 and 1.950..2.100 (straddles
    the end: 50 ms count). Chunks: one begins inside each dispatch, one in a
    prefill phase at 0.300, one before the window inside a dispatch."""
    spans = [
        {"rid": 7, "name": "first_token", "t0": BASE - 1.0, "dur": 0.0},
        {"rid": 8, "name": "first_token", "t0": BASE + 0.5, "dur": 0.0},
        _e("tick", 0.0, 0.2), _e("decode_dispatch", 0.100, 0.010),
        _e("decode_dispatch", 0.600, 0.010),
        _e("decode_dispatch", -0.400, 0.010),
        _e("prefill", 0.290, 0.030),
        _e("first_token_wait", 0.480, 0.020),
        _e("first_token_wait", 0.990, 0.010),
        _e("decode_wait", 0.850, 0.050),
        _q("starved", -0.010, 0.040), _q("starved", 0.500, 0.020),
        _q("starved", 0.900, 0.010), _q("starved", 2.200, 0.100),
        _q("no_work", 1.200, 0.300), _q("no_work", 1.950, 0.150),
        {"rid": 7, "name": "prefill_chunk", "t0": BASE - 0.395, "dur": 0.002},
        {"rid": 8, "name": "prefill_chunk", "t0": BASE + 0.104, "dur": 0.002},
        {"rid": 8, "name": "prefill_chunk", "t0": BASE + 0.300, "dur": 0.002},
        {"rid": 9, "name": "prefill_chunk", "t0": BASE + 0.604, "dur": 0.002},
    ]
    return {"seconds": 2.0, "spans": spans,
            "requests": [{"first_token_t": -1.0}, {"first_token_t": 0.5},
                         {"first_token_t": None}]}


def _read(name, run):
    return R.load_reader(name).read(run)


def test_known_answers_and_clipping_at_both_edges():
    run = handmade()
    assert _read("device_starved.decode", run) == pytest.approx(
        100 * (0.030 + 0.020 + 0.010) / 2.0)
    assert _read("device_starved.prefill", run) == pytest.approx(3.0)
    assert _read("starved_after_first_token.decode", run) == pytest.approx(
        100 * 0.020 / 2.0)
    assert _read("no_work.prefill", run) == pytest.approx(
        100 * (0.300 + 0.050) / 2.0)
    # of the window's three chunks two began inside a dispatch
    assert _read("fused_chunk_share.decode", run) == pytest.approx(200 / 3)
    assert Q.clipped(run, {"t0": -0.5, "dur": 3.0}) == pytest.approx(2.0)
    assert Q.clipped(run, {"t0": 2.5, "dur": 1.0}) == 0.0


@pytest.mark.parametrize("name", list(NEW))
def test_no_engine_row_reads_none(name):
    run = handmade()
    run["spans"] = [s for s in run["spans"] if s["rid"] != S.ENGINE_RID]
    assert _read(name, run) is None
    run["spans"] = []                                  # the untraced run
    assert _read(name, run) is None


@pytest.mark.parametrize("name", QUEUE_METRICS)
def test_a_server_that_declared_its_count_off_reads_none(name):
    run = handmade()
    run["spans"] = [s for s in run["spans"]
                    if s["rid"] != Q.DEVICE_QUEUE_RID]
    assert _read(name, run) is None


@pytest.mark.parametrize("name", list(NEW))
def test_a_program_without_the_row_reads_none_and_does_not_raise(
        name, monkeypatch):
    """The parent of the PR that brought the row: no tuple to declare it."""
    from paddle_tpu import telemetry

    monkeypatch.delattr(telemetry, "DEVICE_QUEUE_SPANS")
    run = handmade()
    run["spans"] = [s for s in run["spans"]
                    if s["rid"] != Q.DEVICE_QUEUE_RID]
    value = _read(name, run)
    if name in QUEUE_METRICS:
        assert value is None
    else:
        # (the fused share reads spans that the parent has too)
        assert value == pytest.approx(200 / 3)


@pytest.mark.parametrize("name", QUEUE_METRICS)
def test_the_capability_with_nothing_in_the_window_reads_zero(name):
    run = handmade()
    run["spans"] = [s for s in run["spans"]
                    if s["rid"] != Q.DEVICE_QUEUE_RID] + [
        _q("no_work", -3.0, 1.0), _q("starved", 2.5, 0.1)]
    assert _read(name, run) == 0.0


@pytest.mark.parametrize("name", QUEUE_METRICS)
def test_names_declared_otherwise_raise_and_do_not_read_zero(name,
                                                             monkeypatch):
    from paddle_tpu import telemetry

    monkeypatch.setattr(telemetry, "DEVICE_QUEUE_SPANS",
                        ("starving", "no_work"))
    with pytest.raises(RuntimeError, match="renamed"):
        _read(name, handmade())


@pytest.mark.parametrize("name,renamed", [
    ("device_starved.decode", "starved"),
    ("no_work.prefill", "no_work"),
    ("starved_after_first_token.decode", "first_token_wait"),
    ("fused_chunk_share.decode", "decode_dispatch"),
    ("fused_chunk_share.decode", "prefill_chunk")])
def test_a_renamed_span_raises_and_does_not_read_zero(name, renamed):
    run = handmade()
    for s in run["spans"]:
        if s["name"] == renamed:
            s["name"] = renamed + "_v2"
    with pytest.raises(RuntimeError, match="renamed"):
        _read(name, run)


def test_no_chunk_in_the_window_reads_none():
    run = handmade()
    run["spans"] = [s for s in run["spans"] if s["name"] != "prefill_chunk"
                    or s["t0"] < BASE]
    assert _read("fused_chunk_share.decode", run) is None


# --------------------------------------------------- the CPU rehearsal
@pytest.fixture(scope="module")
def traced():
    return R.run_cell("tiny-queue-serve", 2**31 + 37, 3.0, True,
                      root=QUEUE_FIXTURES, require_chip=False)


def test_all_five_metrics_come_out_of_run_cell(traced):
    res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == set(NEW)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert all(v["unit"] == "%" for v in res["metrics"].values())
    assert all(isinstance(v, float) and 0.0 <= v <= 100.0
               for v in m.values()), m
    assert m["device_starved.decode"] == m["device_starved.prefill"] > 0
    assert 0 < m["starved_after_first_token.decode"] <= \
        m["device_starved.decode"]
    assert m["device_starved.decode"] + m["no_work.prefill"] <= 100.0
    assert m["fused_chunk_share.decode"] > 0


# ------------------------------------- against the counters, on one run
def test_fused_share_is_the_counters_ratio_on_the_same_run():
    cfg = R.load_json(QUEUE_FIXTURES, "bench", "configs", "tiny-decoder.json")
    traffic = R.load_json(QUEUE_FIXTURES, "bench", "traffic",
                          "tiny-arrivals.json")
    driver = importlib.import_module("benchmarks.drivers.serve_paged")
    scratch = tempfile.mkdtemp(prefix="bench_queue_readers_")
    ctx = R.Context(workload="tiny-queue-serve", seed=2**31 + 41,
                    seconds=3.0, trace=True, config=cfg, traffic=traffic,
                    chips=1, t_process_start=R.T_PROCESS_START,
                    scratch_dir=scratch)
    model, _ = driver.build_model(cfg, ctx.seed)
    srv = driver.build_server(model, cfg, telemetry=True)
    reg = srv.telemetry.registry

    def counts():
        return (reg.get("serving_prefill_chunks_fused").total(),
                reg.get("serving_prefill_chunks_alone").total())

    warm, drain = {}, srv.run

    def run_and_mark():                 # measure() calls run() for warm-up
        out = drain()
        warm["n"] = counts()
        return out

    srv.run = run_and_mark
    run = driver.measure(ctx, srv)
    shutil.rmtree(scratch, ignore_errors=True)
    fused, alone = (a - b for a, b in zip(counts(), warm["n"]))
    assert fused > 0 and alone > 0
    whole = dict(run, seconds=run["t_end"] + 60.0)
    start = S.window_start(run)
    early = min(s["t0"] - start for s in run["spans"]
                if s["name"] == "prefill_chunk")
    # (the ramp's chunks begin before the window: lay the window's start
    # before them, so that the reader counts what the counters counted)
    for r in whole["requests"]:
        if r["first_token_t"] is not None:
            r["first_token_t"] -= early - 1.0
    assert Q.fused_chunk_share(whole) == pytest.approx(
        100.0 * fused / (fused + alone))
    # and the queue row is in the driver's record under its own rid
    assert {s["name"] for s in run["spans"]
            if s["rid"] == Q.DEVICE_QUEUE_RID} == set(Q.NAMES)
    assert srv.telemetry.tracer.dropped == 0


# ------------------------------------------------------ the repo's manifest
def test_the_manifest_gained_the_five_entries_at_its_end():
    m = R.load_manifest(ROOT)
    tail = m["per_layer"][-len(NEW):]
    assert [e["name"] for e in tail] == list(NEW)
    for e in tail:
        layer, moves, cells = NEW[e["name"]]
        assert (e["layer"], e["moves"], e["workloads"]) == (layer, moves,
                                                            cells)
        assert e["source"] == "program_span" and e["unit"] == "%"
        assert e["better"] == ("higher" if e["name"].startswith("fused")
                               else "lower")
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(R.load_reader(e["name"]).read)
    assert len(m["workloads"]) == 5 and len(m["configs"]) == 4


def test_the_fixture_manifest_finds_its_files():
    manifest, base, cfg, traffic, limits, chips = R.load_cell(
        QUEUE_FIXTURES, "tiny-queue-serve")
    assert chips == 1 and cfg["driver"] == "serve_paged"
    assert [e["name"] for e in manifest["per_layer"]] == list(NEW)
    for e in manifest["per_layer"]:
        # found beside run.py: the fixture adds no reader of its own
        assert R.data_file(base, "layer_metrics", e["name"] + ".py") \
            .startswith(os.path.join(ROOT, "benchmarks", "layer_metrics"))
