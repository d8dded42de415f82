"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers need, with nothing but ``jax.profiler.ProfileData``.

A device plane is one named ``/device:TPU:<n>``. On it the line ``XLA Ops``
holds one event per executed operation (fusions, custom calls = Pallas
kernels, collectives, copies) and ``XLA Modules`` one per executed program.
Busy time is the UNION of the op intervals (ops of one device can overlap:
async copies and collectives run beside compute), never their sum.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

from . import stats

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


class Event(tuple):
    """(name, start_s, dur_s)"""
    __slots__ = ()
    name = property(lambda s: s[0])
    start = property(lambda s: s[1])
    dur = property(lambda s: s[2])
    end = property(lambda s: s[1] + s[2])


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [Event]}}; times in seconds on the trace's
    own clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append(Event((ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9)))
    return out


def device_planes(planes) -> List[str]:
    return sorted(p for p in planes if p.startswith("/device:TPU:"))


def ops(planes, plane: str) -> List[Event]:
    return planes[plane].get(OPS_LINE, [])


def modules(planes, plane: str) -> List[Event]:
    return planes[plane].get(MODULES_LINE, [])


def busy_seconds(events: Sequence[Event]) -> float:
    return stats.union_length([(e.start, e.end) for e in events])


def match(events: Sequence[Event], patterns: Sequence[str]) -> List[Event]:
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e.name) for r in rx)]


def idle_gaps(events: Sequence[Event], min_gap: float = 0.0
              ) -> List[Tuple[float, float]]:
    """(start, length) of the intervals in which no op ran."""
    gaps, cur = [], None
    for s, e in sorted((e.start, e.end) for e in events):
        if cur is not None and s - cur > min_gap:
            gaps.append((cur, s - cur))
        cur = e if cur is None else max(cur, e)
    return gaps


def exposed_seconds(events: Sequence[Event]) -> float:
    """Collective time during which no compute op runs on that device."""
    coll = [(e.start, e.end) for e in events if COLLECTIVE.search(e.name)]
    comp = [(e.start, e.end) for e in events if not COLLECTIVE.search(e.name)]
    both = stats.union_length(coll + comp)
    return both - stats.union_length(comp)


_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])?.*?\s([\w\-]+)\(")


def short_name(name: str) -> str:
    """An op event is named by its whole HLO text on the TPU; fold it to
    ``<opcode> <result name> -> <result shape>`` with instance numbers
    dropped, so that the same op of every layer adds up under one key."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"[.\d]+$", "", name)[:120] or name[:120]
    lhs, shape, opcode = m.groups()
    out = opcode if lhs == opcode else f"{opcode} {lhs}"
    return f"{out} -> {shape}" if shape else out


def top_ops(events: Sequence[Event], k: int = 10) -> List[List]:
    """[[name, seconds], ...] of the k kinds of op with most summed time."""
    tot: Dict[str, float] = {}
    for e in events:
        n = short_name(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def label_gaps(ops_ev: Sequence[Event], mods: Sequence[Event],
               k: int = 10) -> List[List]:
    """The idle time between ops, summed by what the device ran before and
    after it: ``<program before> -> <program after>`` (the host did whatever
    lies between two programs: harvest, scheduling, the next dispatch), or
    ``inside <program>`` for a gap within one program."""
    import bisect

    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]

    def module_at(t):
        i = bisect.bisect_right(starts, t + 1e-9) - 1
        if i >= 0 and t <= mods[i].end + 1e-9:
            return re.sub(r"\(.*$", "", mods[i].name)
        return "?"

    tot: Dict[str, float] = {}
    for start, length in idle_gaps(ops_ev):
        a, b = module_at(start), module_at(start + length)
        key = f"inside {a}" if a == b and a != "?" else f"{a} -> {b}"
        tot[key] = tot.get(key, 0.0) + length
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]
