import os

import numpy as np
import pytest

from benchmarks.generators import load, open_loop
from conftest import served_of

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule_other_seed_other_tokens_only(mix):
    t = open_loop.load_traffic(os.path.join(TRAFFIC, mix + ".json"))
    a = open_loop.schedule(t, 32768, 2**31 + 17, 20.0)
    b = open_loop.schedule(t, 32768, 2**31 + 17, 20.0)
    c = open_loop.schedule(t, 32768, 5, 20.0)
    assert [(r.due, r.prompt, r.max_new_tokens) for r in a] == \
           [(r.due, r.prompt, r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # another seed: the same lengths due at the same times, other token ids
    assert [(r.due, len(r.prompt), r.max_new_tokens) for r in a] == \
           [(r.due, len(r.prompt), r.max_new_tokens) for r in c]
    assert load(t["generator"]) is open_loop


@pytest.mark.parametrize("mix", MIXES)
def test_every_block_carries_the_same_work_in_its_own_order(mix):
    """Whole blocks hold the same multiset of lengths and gaps; the order
    inside a block comes from the file's order_seed and differs by block."""
    t = open_loop.load_traffic(os.path.join(TRAFFIC, mix + ".json"))
    n, burst = t["block"], t["ramp"]["burst"]

    r = open_loop.schedule(t, 32768, 1, 200.0)
    b1, b2 = r[burst:burst + n], r[burst + n:burst + 2 * n]
    assert sorted(len(x.prompt) for x in b1) == sorted(len(x.prompt) for x in b2)
    assert sorted(x.max_new_tokens for x in b1) == \
        sorted(x.max_new_tokens for x in b2)
    assert [len(x.prompt) for x in b1] != [len(x.prompt) for x in b2]
    span = b1[-1].due - (-t["ramp"]["seconds"])
    assert span == pytest.approx(n / t["rate_rps"])
    assert b2[-1].due - b1[-1].due == pytest.approx(n / t["rate_rps"])
    other = dict(t, order_seed=t["order_seed"] + 1)
    r2 = open_loop.schedule(other, 32768, 1, 200.0)
    assert [len(x.prompt) for x in r2] != [len(x.prompt) for x in r]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_and_arrivals_follow_the_file(mix):
    t = open_loop.load_traffic(os.path.join(TRAFFIC, mix + ".json"))
    r = open_loop.schedule(t, 32768, 3, 120.0)
    pl = np.array([len(x.prompt) for x in r])
    ol = np.array([x.max_new_tokens for x in r])
    assert pl.min() >= t["prompt"]["min"] and pl.max() <= t["prompt"]["max"]
    assert ol.min() >= t["output"]["min"] and ol.max() <= t["output"]["max"]
    assert np.median(pl) == pytest.approx(t["prompt"]["median"], rel=0.1)
    assert np.median(ol) == pytest.approx(t["output"]["median"], rel=0.1)
    due = np.array([x.due for x in r[t["ramp"]["burst"]:]])
    assert (np.diff(due) > 0).all()
    rate = len(due) / (due[-1] - due[0])
    assert rate == pytest.approx(t["rate_rps"], rel=0.05)
    ids = np.concatenate([x.prompt for x in r])
    assert ids.min() >= 1 and ids.max() < 32768
    # requests fit every server they are sent to
    assert (pl + ol).max() <= min(s["max_len"] for s in served_of(mix))
    # the ramp's burst is due at once, before the window
    assert all(x.due == -t["ramp"]["seconds"] for x in r[:t["ramp"]["burst"]])


def test_rate_override_is_for_the_sweep():
    t = open_loop.load_traffic(os.path.join(TRAFFIC, MIXES[0] + ".json"))
    a = open_loop.schedule(t, 100, 1, 30.0, rate_rps=2.0)
    b = open_loop.schedule(t, 100, 1, 30.0, rate_rps=4.0)
    assert len(b) > 1.5 * (len(a) - t["ramp"]["burst"])
