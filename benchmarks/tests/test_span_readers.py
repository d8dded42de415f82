"""The readers of the program's engine-row spans: on a hand-made run (known
answer; no engine row -> None; a renamed span -> an error, never 0), and on
the fixture cell's driver run with tracing on, found by name."""
import importlib
import shutil
import tempfile

import pytest

from conftest import FIXTURES

from benchmarks import run as R
from benchmarks import span_readers as S

NEW = ["host_ms_per_tick.decode", "host_ms_per_tick.prefill",
       "token_gap_p95_ms.prefill", "prefill_calls_p95.prefill"]
BASE = 5000.0                  # where the span clock stands at window start


def _tick(t0, dur, wait, first_waits=(), chunks=0, rid=7):
    """One tick's spans on the span clock: admit 1 ms, prefill (each chunk a
    1 ms dispatch, each first-token wait inside it), dispatch 2 ms, the
    decode wait, harvest 1 ms, 1 ms of the tick's own."""
    t = BASE + t0
    out = [{"rid": S.ENGINE_RID, "name": "tick", "t0": t, "dur": dur},
           {"rid": S.ENGINE_RID, "name": "admit", "t0": t, "dur": 0.001}]
    p = t + 0.001
    pf = {"rid": S.ENGINE_RID, "name": "prefill", "t0": p, "dur": 0.0}
    out.append(pf)
    for _ in range(chunks):
        out.append({"rid": rid, "name": "prefill_chunk", "t0": p,
                    "dur": 0.001})
        p += 0.001
    for w in first_waits:
        out.append({"rid": S.ENGINE_RID, "name": "first_token_wait",
                    "t0": p, "dur": w})
        p += w
    pf["dur"] = p - pf["t0"]
    out.append({"rid": S.ENGINE_RID, "name": "decode_dispatch", "t0": p,
                "dur": 0.002})
    out.append({"rid": S.ENGINE_RID, "name": "decode_wait", "t0": p + 0.002,
                "dur": wait})
    out.append({"rid": S.ENGINE_RID, "name": "harvest",
                "t0": p + 0.002 + wait, "dur": 0.001})
    assert p + 0.003 + wait <= t + dur
    return out


def handmade():
    """20 ticks back to back from 0.0 s on, 60 ms each with a 44 ms decode
    wait, so the host's 16 ms; tick 4 also waits 4 ms for a first token and
    is 64 ms long; ticks 4, 10 and 11 dispatch 1, 3 and 2 chunks. One tick
    ends before the window and one after it."""
    spans = [{"rid": 7, "name": "first_token", "t0": BASE - 1.5, "dur": 0.0},
             {"rid": 8, "name": "first_token", "t0": BASE + 0.2, "dur": 0.0},
             {"rid": 7, "name": "queued", "t0": BASE - 2.0, "dur": 0.1}]
    spans += _tick(-0.2, 0.1, 0.01)
    t = 0.0
    for i in range(20):
        if i == 4:
            spans += _tick(t, 0.064, 0.044, first_waits=(0.004,), chunks=1)
            t += 0.064
        else:
            spans += _tick(t, 0.060, 0.044, chunks={10: 3, 11: 2}.get(i, 0))
            t += 0.060
    spans += _tick(1.99, 0.08, 0.05)
    return {"seconds": 2.0, "spans": spans,
            "requests": [{"first_token_t": -1.5}, {"first_token_t": 0.2},
                         {"first_token_t": None}]}


def test_known_answers_on_a_handmade_run():
    run = handmade()
    assert len(S.window_ticks(run, S.engine_row(run))) == 20
    assert S.host_ms_per_tick(run) == pytest.approx(16.0, abs=1e-6)
    # a harvest ends 48 ms into its tick, 1 ms later per chunk dispatched
    # and 4 ms later behind the first-token wait: 19 gaps of 60 ms but for
    # 65 and 59 around tick 4 and 63, 59, 58 around ticks 10 and 11.
    # Nearest rank 95 of 19 is the 19th, the longest
    assert S.token_gap_p95_ms(run) == pytest.approx(65.0, abs=1e-6)
    # chunks per tick: 3, 2, 1 and 17 zeros; rank 19 of 20 is the 2
    assert S.prefill_calls_p95(run) == 2.0
    s = S.tick_summary(run)
    assert s["ticks"] == 20 and s["tick_median_ms"] == pytest.approx(60.0)
    assert s["tick_mean_ms"] - s["wait_mean_ms"] == pytest.approx(16.0)


@pytest.mark.parametrize("name", NEW)
def test_no_engine_row_reads_none(name):
    run = handmade()
    run["spans"] = [s for s in run["spans"] if s["rid"] != S.ENGINE_RID]
    assert R.load_reader(name).read(run) is None
    run["spans"] = []                                  # the untraced run
    assert R.load_reader(name).read(run) is None


@pytest.mark.parametrize("name,renamed", [
    ("host_ms_per_tick.decode", "tick"),
    ("host_ms_per_tick.prefill", "decode_wait"),
    ("token_gap_p95_ms.prefill", "harvest"),
    ("prefill_calls_p95.prefill", "tick"),
    ("prefill_calls_p95.prefill", "prefill_chunk")])
def test_a_renamed_span_raises_and_does_not_read_zero(name, renamed):
    run = handmade()
    for s in run["spans"]:
        if s["name"] == renamed:
            s["name"] = renamed + "_v2"
    with pytest.raises(RuntimeError, match="renamed"):
        R.load_reader(name).read(run)


@pytest.fixture(scope="module")
def fixture_run():
    """The fixture cell's driver with tracing on (so telemetry on), at a load
    that keeps prompts arriving while others decode."""
    cfg = R.load_json(FIXTURES, "bench", "configs", "tiny-decoder.json")
    traffic = dict(R.load_json(FIXTURES, "bench", "traffic", "tiny-mix.json"),
                   rate_rps=40.0)
    driver = importlib.import_module("benchmarks.drivers.serve_paged")
    scratch = tempfile.mkdtemp(prefix="bench_span_readers_")
    ctx = R.Context(workload="tiny-serve", seed=2**31 + 5, seconds=2.0,
                    trace=True, config=cfg, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir=scratch)
    run = driver.run(ctx)
    shutil.rmtree(scratch, ignore_errors=True)
    return run


@pytest.mark.parametrize("name", NEW)
def test_readers_found_by_name_read_the_fixture_run(fixture_run, name):
    assert {s["rid"] for s in fixture_run["spans"]} >= {S.ENGINE_RID}
    value = R.load_reader(name).read(fixture_run)
    assert isinstance(value, float) and value > 0


def test_in_program_tick_agrees_with_the_clock_around_step(fixture_run):
    from benchmarks import readers

    s = S.tick_summary(fixture_run)
    outside = readers.window_steps(fixture_run)
    assert abs(s["ticks"] - len(outside)) <= 1
    assert s["tick_mean_ms"] == pytest.approx(readers.tick_ms(fixture_run),
                                              rel=0.1)
    assert S.token_gap_p95_ms(fixture_run) >= s["tick_median_ms"] * 0.5
