"""Read a ``jax.profiler`` trace of a serving process: how busy the device
was, which ops took the time, and what the HOST was doing whenever the
device sat idle.

    python tools/profile_step.py <trace_dir | file.xplane.pb> [--top 10]

A ``GenerationServer`` with telemetry on opens a
``jax.profiler.TraceAnnotation`` around every phase of every tick
(``pt.tick`` > ``pt.admit`` / ``pt.prefill`` > ``pt.first_token_wait`` /
``pt.decode_dispatch`` / ``pt.decode_wait`` / ``pt.harvest``; see
``docs/observability.md``), so a trace taken with
``jax.profiler.start_trace`` carries them on its host plane, on the clock of
its device planes. Every interval in which no op ran on the device is
charged here, second by second, to the innermost ``pt.*`` annotation open on
the host meanwhile (``outside pt.tick`` when none: the caller's own loop),
and shown beside the programs the device ran before and after it. (A gap
between two programs nearly always BEGINS under ``pt.decode_wait`` — the
device finishes before the host learns of it — so the phase open at its
beginning is counted, not charged.)

The engine also says, on its own, when it KNOWS the device's queue to be
empty: ``pt.starved`` / ``pt.no_work`` run from the end of a blocking read
that left no program call in flight to where the next dispatch begins
(they cross phases and ticks, and are kept out of the charging above). Two
lines lay them over the device's real gaps: **soundness**, the share of their
time during which no op ran on the device (a lower bound on idle must never
claim busy time; what is missing is the next program's head where the
trace's two clocks disagree), and
**coverage**, the share of the device's idle time that lies inside them (what
is missing began before the host looked). Where most spans hold the start of
a program that the host had not begun to dispatch, a third line says by how
much the trace's device plane runs early, and both shares with it moved.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import os
import re
import sys
from typing import Dict, List, Tuple

OUTSIDE = "outside pt.tick"
# the device-queue row's annotations (paddle_tpu.telemetry.DEVICE_QUEUE_SPANS)
QUEUE = ("pt.starved", "pt.no_work")
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])?.*?\s([\w\-]+)\(")

Event = Tuple[str, float, float]          # name, start s, end s


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise SystemExit(f"no .xplane.pb under {path}")
    return hits[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: [(name, start, end)]}}, seconds on the trace's clock."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events)
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """The intervals in which at least one of ``events`` ran, merged."""
    out: List[Tuple[float, float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def short_name(name: str) -> str:
    """On the TPU an op event is named by its whole HLO text: fold it to
    ``<opcode> <result> -> <shape>`` so one op of every layer adds up."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[.\d]+$", "", name)[:100] or name[:100]
    lhs, shape, opcode = m.groups()
    out = opcode if lhs == opcode else f"{opcode} {lhs}"
    return f"{out} -> {shape}" if shape else out


def phase_timeline(annotations: List[Event]):
    """(bounds, labels): between ``bounds[i]`` and ``bounds[i + 1]`` the
    innermost open annotation is ``labels[i]`` (the one opened last among
    those open; annotations of one thread nest)."""
    marks = sorted([(e, 0, k) for k, (_, _, e) in enumerate(annotations)]
                   + [(s, 1, k) for k, (_, s, _) in enumerate(annotations)])
    bounds, labels, open_ = [], [], []
    for t, opens, k in marks:
        if not bounds or t > bounds[-1]:
            bounds.append(t)
            labels.append(None)
        if opens:
            open_.append(k)
        else:
            open_.remove(k)
        labels[-1] = annotations[open_[-1]][0] if open_ else OUTSIDE
    return bounds, labels


def charge(bounds, labels, start: float, length: float) -> Dict[str, float]:
    """The interval's seconds by the phase the host was in meanwhile."""
    out: Dict[str, float] = {}
    t, stop = start, start + length
    i = bisect.bisect_right(bounds, t) - 1
    while t < stop:
        nxt = bounds[i + 1] if i + 1 < len(bounds) else stop
        label = labels[i] if 0 <= i < len(labels) else OUTSIDE
        upto = min(max(nxt, t), stop)
        out[label] = out.get(label, 0.0) + upto - t
        t, i = upto, i + 1
    return out


def overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
            ) -> float:
    """Seconds that lie both in ``a`` and in ``b`` (each a sorted list of
    disjoint intervals)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def plane_skew(spans: List[Event], mods: List[Event]) -> List[float]:
    """Per span, the seconds by which a program that began INSIDE it and
    still ran at its end began before that end. The span ends before the
    host begins to dispatch anything, so no program can have begun there:
    each is a lower bound on how far the trace's device plane runs early
    against its host plane."""
    out = []
    for _, s, e in spans:
        began = [m[1] for m in mods if s < m[1] < e < m[2]]
        if began:
            out.append(e - min(began))
    return out


def queue_lines(host: List[Event], runs: List[Tuple[float, float]],
                gaps: List[Tuple[float, float]],
                mods: List[Event] = ()) -> List[str]:
    """Soundness and coverage of the engine's own ``pt.starved`` /
    ``pt.no_work`` against the device's gaps, between its first and its
    last op; and, where the trace's two planes disagree, by how much."""
    first, last = runs[0][0], runs[-1][1]
    idle = [(t, t + length) for t, length in gaps]
    out = []
    known_all: List[Event] = []
    for name in QUEUE:
        evs = [(n, max(s, first), min(e, last)) for n, s, e in host
               if n == name and e > first and s < last]
        known_all += evs
        known = union(evs)
        sec = sum(e - s for s, e in known)
        out.append(f"  {name}: {len(evs)} spans, {sec:.4f} s, of which "
                   f"{overlap(known, idle):.4f} s with no op on the device")
    known = union(known_all)
    sec, idle_s = sum(e - s for s, e in known), sum(e - s for s, e in idle)
    inside = overlap(known, idle)
    if not known_all:
        return ["  no pt.starved / pt.no_work annotation: a program "
                "without the device-queue row, or telemetry off"]
    # where the busy part of a span lies: before its first idle moment (the
    # device still ran when the read returned), after its last (the next
    # program began before the span ended), or between
    head = tail = 0.0
    for s, e in known:
        mine = [(max(a, s), min(b, e)) for a, b in idle if b > s and a < e]
        if mine:
            head += mine[0][0] - s
            tail += e - mine[-1][1]
    return out + [
        f"  soundness {100 * inside / sec:.1f} % of the {sec:.4f} s the "
        f"engine called the queue empty, no op ran (ops ran for "
        f"{head:.4f} s at the spans' beginnings, {tail:.4f} s at their "
        f"ends, {max(sec - inside - head - tail, 0.0):.4f} s between)",
        f"  coverage {100 * inside / idle_s:.1f} % of the device's "
        f"{idle_s:.4f} s of idle time lie inside them" if idle_s > 0
        else "  coverage: the device had no idle time"
    ] + skew_lines(known_all, mods, idle, sec, idle_s)


def skew_lines(spans: List[Event], mods: List[Event],
               idle: List[Tuple[float, float]], sec: float,
               idle_s: float) -> List[str]:
    early = sorted(plane_skew(spans, mods))
    if 2 * len(early) <= len(spans) or idle_s <= 0:
        return []
    shift = early[0]                  # the least: what every span agrees on
    moved = [(a + shift, b + shift) for a, b in idle]
    inside = overlap(union(spans), moved)
    return [
        f"  clock check: in {len(early)} of {len(spans)} spans the next "
        f"program begins on the device plane {early[0] * 1e3:.3f}-"
        f"{early[-1] * 1e3:.3f} ms BEFORE the span ends, which is before "
        f"the host began to dispatch it: this trace's device plane runs at "
        f"least {shift * 1e3:.3f} ms early against its host plane; with "
        f"the device plane moved by that, soundness "
        f"{100 * inside / sec:.1f} %, coverage "
        f"{100 * inside / idle_s:.1f} %"]


def program_at(mods: List[Event], starts: List[float], t: float):
    """(which run, name) of the program running at ``t``."""
    i = bisect.bisect_right(starts, t + 1e-9) - 1
    if i >= 0 and t <= mods[i][2] + 1e-9:
        return i, re.sub(r"\(.*$", "", mods[i][0])
    return None, "?"


def report(planes, top: int, host_prefix: str) -> List[str]:
    devices = sorted(p for p in planes if p.startswith("/device:TPU:"))
    if not devices:
        raise SystemExit(f"no /device:TPU:<n> plane in the trace; planes: "
                         f"{sorted(planes)}")
    busy = {d: sum(e - s for s, e in union(planes[d].get("XLA Ops", [])))
            for d in devices}
    dev = max(devices, key=busy.get)
    ops = planes[dev].get("XLA Ops", [])
    if not ops:
        raise SystemExit(f"{dev} ran no op inside the trace")
    runs = union(ops)
    span = runs[-1][1] - runs[0][0]
    gaps = [(a[1], b[0] - a[1]) for a, b in zip(runs, runs[1:])]
    idle = sum(g for _, g in gaps)
    out = [f"device plane {dev} (the busiest of {len(devices)}): first op to "
           f"last op {span:.3f} s, busy {busy[dev]:.3f} s = "
           f"{100 * busy[dev] / span:.1f} %, idle {idle:.3f} s in "
           f"{len(gaps)} gaps", "", f"top ops (seconds, share of busy):"]
    tot: Dict[str, float] = {}
    for name, s, e in ops:
        n = short_name(name)
        tot[n] = tot.get(n, 0.0) + e - s
    for n, t in sorted(tot.items(), key=lambda x: -x[1])[:top]:
        out.append(f"  {t:8.3f}  {100 * t / busy[dev]:5.1f} %  {n}")

    host = [ev for p, lines in planes.items() if p.startswith("/host:")
            for evs in lines.values() for ev in evs]
    # (the queue row's annotations cross phases: not part of the nesting)
    anns = [ev for ev in host if ev[0].startswith("pt.")
            and ev[0] not in QUEUE]
    bounds, labels = phase_timeline(anns)
    mods = sorted(planes[dev].get("XLA Modules", []), key=lambda m: m[1])
    starts = [m[1] for m in mods]
    by_phase: Dict[str, float] = {}
    begun: Dict[str, int] = {}
    by_pair: Dict[Tuple[str, str], float] = {}
    for t, length in gaps:
        (i, a), (j, b) = (program_at(mods, starts, t),
                          program_at(mods, starts, t + length))
        # (two runs of one program in a row are not one run)
        around = f"inside {a}" if i == j and i is not None else f"{a} -> {b}"
        shares = charge(bounds, labels, t, length)
        first = next(iter(shares))
        begun[first] = begun.get(first, 0) + 1
        for phase, sec in shares.items():
            by_phase[phase] = by_phase.get(phase, 0.0) + sec
            by_pair[(around, phase)] = by_pair.get((around, phase), 0.0) + sec
    out += ["", "idle time by host phase (each gap's seconds go to the "
            "innermost pt.* annotation open on the host meanwhile;",
            "'gaps begun' counts the gaps by the phase open when they "
            "began):",
            f"  {'seconds':>8}  {'share':>7}  {'gaps begun':>10}  phase"]
    for phase, sec in sorted(by_phase.items(), key=lambda x: -x[1]):
        out.append(f"  {sec:8.4f}  {100 * sec / idle:5.1f} %  "
                   f"{begun.get(phase, 0):10d}  {phase}")
    out.append(f"  {idle:8.4f}  100.0 %  {len(gaps):10d}  total")
    out += ["", "the same, by the programs the device ran around the gap:"]
    pairs: Dict[str, float] = {}
    for (around, _), t in by_pair.items():
        pairs[around] = pairs.get(around, 0.0) + t
    for around, t in sorted(pairs.items(), key=lambda x: -x[1])[:top]:
        parts = sorted(((ph, sec) for (ar, ph), sec in by_pair.items()
                        if ar == around), key=lambda x: -x[1])
        out.append(f"  {t:8.4f}  {around}: " + ", ".join(
            f"{ph} {sec:.4f}" for ph, sec in parts))

    out += ["", "the engine's own device-queue spans (a LOWER bound on "
            "idle: the queue known empty, read to next dispatch):"]
    out += queue_lines(host, runs, gaps, mods)
    out += ["", "host annotations (count, seconds):"]
    seen: Dict[str, List[float]] = {}
    for name, s, e in host:
        if name.startswith("pt.") or (host_prefix
                                      and name.startswith(host_prefix)):
            seen.setdefault(name, []).append(e - s)
    if not anns:
        out.append("  no pt.* annotation: was the server's telemetry on "
                   "while the trace ran?")
    for name, ds in sorted(seen.items(), key=lambda x: -sum(x[1])):
        out.append(f"  {len(ds):6d}  {sum(ds):8.3f}  {name}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory of jax.profiler, or "
                                  "an .xplane.pb file")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--host-prefix", default="",
                    help="also count the host annotations whose name starts "
                         "so (the caller's own, around step())")
    args = ap.parse_args(argv)
    path = find_xplane(args.trace)
    print(f"trace: {path}")
    print("\n".join(report(load(path), args.top, args.host_prefix)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
