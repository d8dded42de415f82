"""What the readers of the program's own spans share (``run["spans"]``: every
``SpanTracer`` span of the run's requests and of the engine row, as
``rid / name / t0 / dur`` on the program's span clock).

The engine row (``paddle_tpu.telemetry.ENGINE_RID``) holds one ``tick`` span
per ``GenerationServer.step()`` and, inside it, the tick's phases: ``admit``,
``prefill`` (with a ``first_token_wait`` per prompt that ended), then
``decode_dispatch``, ``decode_wait``, ``harvest``. Children are found by time
containment; the window is laid on the span clock by the first token of the
run, which the program stamps as a request mark and as a ``first_token``
instant from one reading of one clock.

A reader returns None where the program has no engine row (the harness then
leaves the metric out) and RAISES where the row is there without a span name
the reader needs: a renamed span must not read as 0.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from . import stats

ENGINE_RID = -2
WAITS = ("decode_wait", "first_token_wait")


def window_start(run) -> float:
    """The window's start on the span clock: the run's earliest first token
    as the program's span has it, less the same moment as the benchmark has
    it (the request mark, counted from the window's start)."""
    stamped = [s["t0"] for s in run["spans"] if s["name"] == "first_token"]
    marked = [r["first_token_t"] for r in run["requests"]
              if r["first_token_t"] is not None]
    if not stamped or not marked:
        raise RuntimeError("spans but no first token to lay the window on "
                           "the span clock with")
    return min(stamped) - min(marked)


def engine_row(run) -> Optional[List[dict]]:
    """The engine row's spans with ``t0`` counted from the window's start,
    sorted by it; None where there is no engine row."""
    row = [s for s in run.get("spans") or [] if s["rid"] == ENGINE_RID]
    if not row:
        return None
    start = window_start(run)
    return sorted((dict(s, t0=s["t0"] - start) for s in row),
                  key=lambda s: s["t0"])


def need(row: List[dict], *names: str) -> None:
    have = {s["name"] for s in row}
    missing = [n for n in names if n not in have]
    if missing:
        raise RuntimeError(f"the engine row holds no span named {missing} "
                           f"(it holds {sorted(have)}): renamed or removed — "
                           f"the yardstick must not read 0")


def end(s: dict) -> float:
    return s["t0"] + s["dur"]


def in_window(run, spans: List[dict]) -> List[dict]:
    """Those that END inside [0, seconds), like the host-clock readers."""
    return [s for s in spans if 0.0 <= end(s) < run["seconds"]]


def window_ticks(run, row: List[dict]) -> List[dict]:
    """The window's ``tick`` spans, each with its ``children`` (every other
    engine-row span that lies inside it)."""
    ticks = in_window(run, [s for s in row if s["name"] == "tick"])
    rest = [s for s in row if s["name"] != "tick"]
    out, i = [], 0
    for t in ticks:
        while i < len(rest) and rest[i]["t0"] < t["t0"]:
            i += 1
        j = i
        kids = []
        while j < len(rest) and rest[j]["t0"] <= end(t):
            if end(rest[j]) <= end(t) + 1e-9:
                kids.append(rest[j])
            j += 1
        out.append(dict(t, children=kids))
    return out


def waited(tick: dict) -> float:
    """Seconds of a tick (of ``window_ticks``) spent waiting for the device."""
    return sum(c["dur"] for c in tick["children"] if c["name"] in WAITS)


def host_ms_per_tick(run) -> Optional[float]:
    """Mean over the window's ticks of the tick less its two waits for the
    device: what the host itself took."""
    row = engine_row(run)
    if row is None:
        return None
    need(row, "tick", "decode_wait")
    ticks = window_ticks(run, row)
    if not ticks:
        return None
    return stats.mean([t["dur"] - waited(t) for t in ticks]) * 1e3


def token_gap_p95_ms(run) -> Optional[float]:
    """p95 of the time between the ends of successive ``harvest`` spans: the
    moments at which every decoding request gets its next token."""
    row = engine_row(run)
    if row is None:
        return None
    need(row, "harvest")
    ends = sorted(end(s) for s in in_window(
        run, [s for s in row if s["name"] == "harvest"]))
    gaps = [b - a for a, b in zip(ends, ends[1:])]
    return stats.percentile(gaps, 95) * 1e3 if gaps else None


def prefill_calls_p95(run) -> Optional[float]:
    """p95 over the window's ticks of the ``prefill_chunk`` spans begun
    inside the tick: program calls that each re-read the weights."""
    row = engine_row(run)
    if row is None:
        return None
    need(row, "tick")
    start = window_start(run)
    starts = sorted(s["t0"] - start for s in run["spans"]
                    if s["name"] == "prefill_chunk")
    if not starts:
        raise RuntimeError("an engine row but no prefill_chunk span in the "
                           "whole run: renamed or removed")
    ticks = window_ticks(run, row)
    if not ticks:
        return None
    calls = [bisect.bisect_right(starts, end(t))
             - bisect.bisect_left(starts, t["t0"]) for t in ticks]
    return float(stats.percentile(calls, 95))


def tick_summary(run) -> Optional[Dict[str, float]]:
    """The in-program tick beside the benchmark's own clock around
    ``step()``, for ``tools/traced_run.py``: count, median and mean of the
    window's ``tick`` spans, and the mean of their waits."""
    row = engine_row(run)
    if row is None:
        return None
    ticks = window_ticks(run, row)
    if not ticks:
        return None
    durs = sorted(t["dur"] for t in ticks)
    return {"ticks": len(ticks), "tick_median_ms": durs[len(durs) // 2] * 1e3,
            "tick_mean_ms": stats.mean(durs) * 1e3,
            "wait_mean_ms": stats.mean([waited(t) for t in ticks]) * 1e3}
