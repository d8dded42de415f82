"""The reduction from a profiler trace to busy time, idle gaps, matched
kernels and exposed collective time: on synthetic events whose answers are
known by hand, and on a small trace recorded on the v5e (kept beside this
file)."""
import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixtures", "recorded_v5e.xplane.pb")


def ev(name, start, dur):
    return Event((name, float(start), float(dur)))


OPS = [ev("fusion.1", 0.0, 1.0), ev("paged_decode_attention.3", 1.0, 2.0),
       ev("copy-start.2", 2.5, 1.0),            # overlaps the kernel
       ev("all-reduce.7", 5.0, 2.0),            # 5..7
       ev("fusion.9", 6.0, 2.0),                # 6..8 hides half of it
       ev("fusion.1", 10.0, 1.0)]


def test_busy_is_the_union_not_the_sum():
    assert tr.busy_seconds(OPS) == pytest.approx(3.5 + 3.0 + 1.0)
    assert sum(e.dur for e in OPS) == pytest.approx(9.0)


def test_idle_gaps_and_their_labels():
    assert tr.idle_gaps(OPS) == [(3.5, 1.5), (8.0, 2.0)]
    mods = [ev("jit_decode(123)", 0.0, 3.5), ev("jit_prefill(9)", 5.0, 3.0),
            ev("jit_decode(123)", 10.0, 1.0)]
    labels = dict(map(tuple, tr.label_gaps(OPS, mods)))
    assert labels == {"jit_decode -> jit_prefill": pytest.approx(1.5),
                      "jit_prefill -> jit_decode": pytest.approx(2.0)}


def test_event_matching_and_top_ops():
    assert [e.name for e in tr.match(OPS, [r"paged_decode_attention"])] == \
        ["paged_decode_attention.3"]
    assert tr.match(OPS, [r"no_such_kernel"]) == []
    top = tr.top_ops(OPS, k=2)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(4.0)


def test_exposed_collective_time():
    # all-reduce 5..7, compute 6..8: one second of it is exposed
    assert tr.exposed_seconds(OPS) == pytest.approx(1.0)


def test_roofline_reader_raises_when_nothing_matches():
    from benchmarks.readers import kernel_roofline

    run = {"device_ops": OPS, "peaks": {"bf16_flops": 1e12,
                                        "hbm_bytes_per_s": 1e9}}
    with pytest.raises(RuntimeError, match="no trace event matches"):
        kernel_roofline(run, [r"no_such_kernel"], 1.0, 1.0)
    # 2e9 bytes at 1e9 B/s = 2 s least, over the kernel's 2 s: 100 %
    assert kernel_roofline(run, [r"paged_decode_attention"], 1.0, 2e9) == \
        pytest.approx(100.0)
    assert run["roofline_bounds"] == {"paged_decode_attention": "memory"}


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this tree")
def test_recorded_v5e_trace():
    planes = tr.load(RECORDED)
    dev = tr.device_planes(planes)
    assert dev and dev[0] == "/device:TPU:0"
    ops = tr.ops(planes, dev[0])
    assert ops and tr.modules(planes, dev[0])
    a, b = min(e.start for e in ops), max(e.end for e in ops)
    busy = tr.busy_seconds(ops)
    assert 0 < busy <= (b - a) * (1 + 1e-9)
    assert busy <= sum(e.dur for e in ops) * (1 + 1e-9)
    gaps = sum(g for _, g in tr.idle_gaps(ops))
    assert busy + gaps == pytest.approx(b - a, rel=1e-6)
