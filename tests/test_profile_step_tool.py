"""tools/profile_step.py: idle gaps of the device charged to the innermost
``pt.*`` host annotation open when the gap began."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import profile_step as P  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmarks", "tests", "fixtures",
                        "recorded_v5e.xplane.pb")


def _planes():
    """Two ticks. Device: decode program 0.010-0.060, a chunk program
    0.064-0.070, decode 0.072-0.120 with a hole 0.090-0.091 inside it; then
    nothing until a decode at 0.200-0.210 that the host dispatched outside
    any tick."""
    ops = [("%fusion.1 = bf16[8,8] fusion(", 0.010, 0.060),
           ("%fusion.2 = bf16[8,8] fusion(", 0.064, 0.070),
           ("%custom-call.3 = bf16[4,8] custom-call(", 0.072, 0.090),
           ("%custom-call.4 = bf16[4,8] custom-call(", 0.091, 0.120),
           ("%fusion.5 = bf16[8,8] fusion(", 0.200, 0.210)]
    mods = [("jit_decode(1)", 0.010, 0.060), ("jit_chunk(2)", 0.064, 0.070),
            ("jit_decode(1)", 0.072, 0.120), ("jit_decode(1)", 0.200, 0.210)]
    host = [("pt.tick", 0.000, 0.063), ("pt.decode_wait", 0.012, 0.061),
            ("pt.harvest", 0.061, 0.0625),
            ("pt.tick", 0.0632, 0.125), ("pt.prefill", 0.0633, 0.071),
            ("pt.first_token_wait", 0.0650, 0.0705),
            ("pt.decode_dispatch", 0.0711, 0.073),
            ("pt.decode_wait", 0.073, 0.121),
            ("bench.step", 0.0, 0.0631), ("bench.step", 0.0632, 0.126)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
            "/device:TPU:1": {"XLA Ops": ops[:1], "XLA Modules": mods[:1]},
            "/host:CPU": {"python3": host, "main/1": [("Execute", 0.0, 0.1)]}}


def test_innermost_annotation_open_at_each_moment():
    anns = _planes()["/host:CPU"]["python3"][:-2]
    bounds, labels = P.phase_timeline(anns)
    got = [next(iter(P.charge(bounds, labels, t, 1e-6)))
           for t in (0.005, 0.060, 0.0631, 0.068, 0.0707, 0.0905, 0.124,
                     0.150, -1.0)]
    assert got == ["pt.tick", "pt.decode_wait", P.OUTSIDE,
                   "pt.first_token_wait", "pt.prefill", "pt.decode_wait",
                   "pt.tick", P.OUTSIDE, P.OUTSIDE]


def test_a_gap_is_split_over_the_phases_the_host_went_through():
    anns = _planes()["/host:CPU"]["python3"][:-2]
    got = P.charge(*P.phase_timeline(anns), 0.060, 0.004)
    # 0.060-0.061 still waiting, harvest to 0.0625, the tick's own tail to
    # 0.063, the caller's loop to 0.0632, the next tick's head, its prefill
    assert list(got) == ["pt.decode_wait", "pt.harvest", "pt.tick",
                         P.OUTSIDE, "pt.prefill"]
    assert got["pt.harvest"] == pytest.approx(0.0015)
    assert got[P.OUTSIDE] == pytest.approx(0.0002)
    assert got["pt.tick"] == pytest.approx(0.0005 + 0.0001)
    assert sum(got.values()) == pytest.approx(0.004)


def test_report_charges_every_gap_and_adds_up():
    lines = P.report(_planes(), top=5, host_prefix="bench.")
    text = "\n".join(lines)
    assert lines[0].startswith("device plane /device:TPU:0 (the busiest of 2)")
    # gaps: 0.060-0.064 (split as above), 0.070-0.072 from the first-token
    # wait into the decode dispatch, 0.090-0.091 inside the decode program,
    # 0.120-0.200 from the decode wait out into the caller's loop
    assert "idle 0.087 s in 4 gaps" in lines[0]
    at = next(i for i, ln in enumerate(lines)
              if ln.startswith("idle time by host phase"))
    rows = {ln.split(None, 4)[-1]: (float(ln.split()[0]), int(ln.split()[3]))
            for ln in lines[at + 3:lines.index("", at)]}
    assert rows["total"] == (pytest.approx(0.087, abs=1e-4), 4)
    assert sum(v[0] for k, v in rows.items() if k != "total") == \
        pytest.approx(0.087, abs=2e-4)
    assert rows["pt.decode_wait"][1] == 3
    assert rows["pt.first_token_wait"] == (pytest.approx(0.0005, abs=1e-5), 1)
    assert rows[P.OUTSIDE][0] == pytest.approx(0.0002 + 0.075, abs=1e-4)
    assert "jit_decode -> jit_chunk: pt.harvest 0.0015" in text
    assert "inside jit_decode: pt.decode_wait 0.0010" in text
    assert "jit_decode -> jit_decode: outside pt.tick 0.0750" in text
    at = lines.index("host annotations (count, seconds):")
    counts = {ln.split()[-1]: int(ln.split()[0]) for ln in lines[at + 1:]
              if ln.split()[-1] in ("pt.tick", "bench.step")}
    assert counts == {"pt.tick": 2, "bench.step": 2}
    assert "custom-call -> bf16[4,8]" in text


def test_reads_a_recorded_trace_with_no_annotation_as_outside():
    planes = P.load(P.find_xplane(RECORDED))
    lines = P.report(planes, top=3, host_prefix="")
    assert any(ln.rstrip().endswith(P.OUTSIDE) for ln in lines)
    assert any("no pt.* annotation" in ln for ln in lines)
    with pytest.raises(SystemExit):
        P.report({"/host:CPU": {}}, top=3, host_prefix="")
