"""A capacity cell stays above its knee, and says how far above.

A cell judged on ``serve_tok_s`` (``unfinished_fails`` false) measures
capacity only while no slot waits for work. Below the knee it measures the
schedule, and a FASTER server reads LOWER there (PERF.md section 6, PR 28 and
PR 29). Three guards, all on the CPU:

* every traffic file's rate is its recorded knee times its recorded factor;
* a toy server fitted to the file's knee and then made 1.4 x faster, replaying
  the file's own schedule, keeps a queue behind its slots in every step of a
  50 s window — so the next edit of a rate or a ramp cannot put a capacity
  cell under its knee unseen;
* the reader of ``queue_depth_min.decode``, the counter that says on the chip
  how much of that room is left.
"""
import importlib
import math
import os
import shutil
import tempfile

import pytest

from benchmarks import readers
from benchmarks import run as R
from benchmarks.generators import open_loop
from conftest import FIXTURES, ROOT, served_of

TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))
WINDOW_S = 50.0
FASTER = 1.4


def traffic_of(mix):
    return open_loop.load_traffic(os.path.join(TRAFFIC, mix + ".json"))


CAPACITY_MIXES = [m for m in MIXES
                  if not traffic_of(m).get("unfinished_fails", True)]


@pytest.mark.parametrize("mix", MIXES)
def test_the_rate_is_the_recorded_knee_times_the_recorded_factor(mix):
    t = traffic_of(mix)
    assert t["rate_rps"] == pytest.approx(t["knee_factor"] * t["knee_rps"],
                                          rel=0.02)
    if not t.get("unfinished_fails", True):
        assert t["knee_factor"] >= 1.25, "a capacity cell is offered above its knee"
        assert t["ramp"]["burst"] >= max(s["max_batch"] for s in served_of(mix))
    else:
        assert t["knee_factor"] <= 1.0, "a latency cell is offered below its knee"


class ToyServer:
    """Continuous batching in a dozen lines: ``slots`` slots, FIFO queue, one
    prefill chunk per prefilling slot per step, one token per decoding slot
    per step. A step takes ``chunk_s`` a chunk call plus a decode tick that
    is half fixed (the weights) and half per row (attention) — the shape the
    ledger's ticks have (18.4 ms at 27 rows, 25.4 at 64). ``tick_full_s`` is
    fitted so that, with every slot full, it completes ``knee_rps`` requests
    a second of the mix's mean request."""

    CHUNK_OVER_TICK = 0.55      # 14 ms a chunk call against a 25.4 ms tick

    def __init__(self, traffic, served, speedup=1.0):
        self.slots, self.chunk = served["max_batch"], served["prefill_chunk"]
        n = int(traffic["block"])
        chunks = [math.ceil(p / self.chunk)
                  for p in open_loop._lengths(traffic["prompt"], n)]
        outs = open_loop._lengths(traffic["output"], n)
        per_request = (self.CHUNK_OVER_TICK * sum(chunks) / n
                       + float(outs.mean()) / self.slots)
        self.tick_full_s = 1.0 / (traffic["knee_rps"] * per_request) / speedup
        self.chunk_s = self.CHUNK_OVER_TICK * self.tick_full_s

    def tick_s(self, rows):
        return self.tick_full_s * (0.5 + 0.5 * rows / self.slots)

    def replay(self, reqs, seconds):
        """``(t1, queue_depth, slots_occupied, tokens)`` after every step."""
        t, nxt, queue, slot, steps = reqs[0].due, 0, [], [], []
        while t < seconds:
            while nxt < len(reqs) and reqs[nxt].due <= t:
                queue.append(reqs[nxt])
                nxt += 1
            if not queue and not slot:
                if nxt == len(reqs):
                    break
                t = reqs[nxt].due
                continue
            while queue and len(slot) < self.slots:
                r = queue.pop(0)
                slot.append([len(r.prompt), r.max_new_tokens])
            rows = [s for s in slot if s[0] <= 0]
            prefilling = [s for s in slot if s[0] > 0]
            tokens = len(rows)
            for s in rows:
                s[1] -= 1
            for s in prefilling:
                s[0] -= self.chunk
                if s[0] <= 0:              # the last chunk gives the first token
                    s[1] -= 1
                    tokens += 1
            t += len(prefilling) * self.chunk_s + (
                self.tick_s(len(rows)) if rows else 0.0)
            occupied = len(slot)
            slot = [s for s in slot if s[1] > 0]
            steps.append((t, len(queue), occupied, tokens))
        return steps


def window(steps):
    return [s for s in steps if 0.0 <= s[0] < WINDOW_S]


def window_tokens(traffic, served, speedup, reqs):
    return sum(s[3] for s in window(
        ToyServer(traffic, served, speedup).replay(reqs, WINDOW_S)))


@pytest.mark.parametrize("mix", CAPACITY_MIXES)
def test_a_1_4x_faster_server_still_has_a_queue_in_every_step(mix):
    t = traffic_of(mix)
    reqs = open_loop.schedule(t, 100, 1, WINDOW_S)
    for served in served_of(mix):
        for speedup in (1.0, FASTER):
            st = window(ToyServer(t, served, speedup).replay(reqs, WINDOW_S))
            assert len(st) > 500
            assert min(q for _, q, _, _ in st) > 0, \
                f"{mix}: a slot waits for work at {speedup} x the fitted speed"
            assert min(o for _, _, o, _ in st) == served["max_batch"]


def test_the_replay_catches_the_rate_pr26_left_under_its_knee():
    """The decode mix as it stood from PR 23 to PR 28: 1.25 x a knee of
    1.4 /s. PR 26 cut the full tick from 76 ms to 25 and nothing said that
    the cell had left its knee."""
    t = dict(traffic_of("reasoning-decode"), rate_rps=1.75, knee_rps=1.4,
             knee_factor=1.25)
    reqs = open_loop.schedule(t, 100, 1, WINDOW_S)
    (served,) = served_of("reasoning-decode")

    st = window(ToyServer(t, served, 3.0).replay(reqs, WINDOW_S))
    assert min(q for _, q, _, _ in st) == 0
    assert min(o for _, _, o, _ in st) < served["max_batch"]
    # and there a faster server reads LOWER (PR 28): what it emits of the
    # burst before the window opens is not counted in it
    assert window_tokens(t, served, 3.0 * 1.25, reqs) < \
        window_tokens(t, served, 3.0, reqs)


def test_above_the_knee_a_faster_server_reads_higher_by_as_much():
    t = traffic_of("reasoning-decode")
    reqs = open_loop.schedule(t, 100, 1, WINDOW_S)
    (served,) = served_of("reasoning-decode")
    gain = window_tokens(t, served, 1.25, reqs) / \
        window_tokens(t, served, 1.0, reqs)
    assert gain == pytest.approx(1.25, rel=0.03)


def step(t1, depth, dur=0.02):
    return {"t0": t1 - dur, "t1": t1, "queue_depth": depth,
            "slots_occupied": 4, "slots_total": 4}


def test_queue_depth_min_reads_the_windows_steps_only():
    read = R.load_reader("queue_depth_min.decode").read
    run = {"seconds": 10.0,
           "steps": [step(-1.0, 0), step(0.5, 9), step(4.0, 3), step(9.99, 7),
                     step(10.0, 0), step(12.0, 1)]}
    assert read(run) == 3.0 and isinstance(read(run), float)
    run["steps"][2]["queue_depth"] = 0
    assert read(run) == 0.0                   # 0 is a reading, not "nothing"
    # nothing to read: no step in the window, or a driver without the counter
    assert read({"seconds": 10.0, "steps": [step(-1.0, 5), step(11.0, 5)]}) is None
    assert read({"seconds": 10.0,
                 "steps": [{"t0": 0.1, "t1": 0.2}]}) is None
    m = R.load_manifest(ROOT)
    met = next(p for p in m["per_layer"] if p["name"] == "queue_depth_min.decode")
    assert met["moves"] == "serve_tok_s" and met["layer"] == "scheduler"
    capacity_cells = sorted(w["name"] for w in m["workloads"]
                            if w["traffic"] in CAPACITY_MIXES)
    assert sorted(met["workloads"]) == capacity_cells


def test_queue_depth_min_on_the_fixture_cells_driver_run():
    """The fixture cell's driver overloaded as a capacity cell is (a burst
    over its 4 slots, arrivals above what it serves, nothing drained): the
    reader gives the least depth the driver recorded, and it is above 0."""
    cfg = R.load_json(FIXTURES, "bench", "configs", "tiny-decoder.json")
    traffic = dict(R.load_json(FIXTURES, "bench", "traffic", "tiny-mix.json"),
                   rate_rps=400.0, ramp={"seconds": 0.5, "burst": 8},
                   drain_s=0.0, unfinished_fails=False)
    driver = importlib.import_module("benchmarks.drivers.serve_paged")
    scratch = tempfile.mkdtemp(prefix="bench_capacity_")
    ctx = R.Context(workload="tiny-serve", seed=2**31 + 29, seconds=1.0,
                    trace=False, config=cfg, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir=scratch)
    run = driver.run(ctx)
    shutil.rmtree(scratch, ignore_errors=True)
    st = readers.window_steps(run)
    value = R.load_reader("queue_depth_min.decode").read(run)
    assert value == min(s["queue_depth"] for s in st) and value > 0
    assert driver.attempted_failed(run)[1] == 0
