"""What the readers of the program's device-queue row share, beside
``span_readers.py`` (whose helpers they use; ``run["spans"]`` as there).

The device-queue row (``paddle_tpu.telemetry.DEVICE_QUEUE_RID``) holds one
span for every stretch over which the engine KNEW the device had nothing to
run: from the end of a blocking read that left no program call in flight to
where the next call's dispatch begins. ``starved``: the server had work (an
occupied slot, a queued request) and the host had not dispatched it;
``no_work``: it had none. The drivers keep ``rid / name / t0 / dur`` only, so
the cause of a span is recovered here from the engine-row wait that ends
where the span begins.

Every reading is a LOWER bound on the device's idle time: a queue that runs
dry before the host looks is not seen until the host looks. Read over the
whole window (``run["seconds"]``), a span that straddles an edge clipped to
it — not over the 4 s trace that ``device_idle.*`` samples after the window.

A reader returns None where the run holds no device-queue capability — no
engine row, a program that declares no such row (before PR 37), or a server
that declared its count off (no span on the row in the whole run: a server
that keeps the count opens one the moment it is built). It reads 0.0 where
the capability is there and no span fell in the window, and RAISES where the
program's declared names are not the names read here: a renamed span must
not read as 0.
"""
from __future__ import annotations

import bisect
from typing import List, Optional

from . import span_readers as S

DEVICE_QUEUE_RID = -3
STARVED, NO_WORK = "starved", "no_work"
NAMES = (STARVED, NO_WORK)
FIRST_TOKEN_WAIT = "first_token_wait"
# a span "begins where a wait ends": one reading of one clock on both, so
# equal up to the rounding of t0 + dur
_TOUCH_S = 1e-6


def declared():
    """(rid, names) of the device-queue row as the program declares them;
    None for a program without one."""
    from paddle_tpu import telemetry

    names = getattr(telemetry, "DEVICE_QUEUE_SPANS", None)
    if names is None:
        return None
    return telemetry.DEVICE_QUEUE_RID, tuple(names)


def queue_row(run) -> Optional[List[dict]]:
    """The device-queue row's spans with ``t0`` counted from the window's
    start, sorted by it; None where the run has no such capability."""
    if S.engine_row(run) is None:
        return None
    dec = declared()
    if dec is None:
        return None
    rid, names = dec
    if rid != DEVICE_QUEUE_RID or set(names) != set(NAMES):
        raise RuntimeError(
            f"the program declares device-queue row {rid} with spans "
            f"{names}; read here: row {DEVICE_QUEUE_RID}, {NAMES} — renamed "
            f"or removed: the yardstick must not read 0")
    row = [s for s in run["spans"] if s["rid"] == DEVICE_QUEUE_RID]
    if not row:
        return None                     # the server's count is declared off
    unknown = sorted({s["name"] for s in row} - set(NAMES))
    if unknown:
        raise RuntimeError(f"the device-queue row holds spans named "
                           f"{unknown}, unknown here: renamed")
    start = S.window_start(run)
    return sorted((dict(s, t0=s["t0"] - start) for s in row),
                  key=lambda s: s["t0"])


def clipped(run, s: dict) -> float:
    """Seconds of span ``s`` inside the window [0, seconds)."""
    return max(0.0, min(S.end(s), run["seconds"]) - max(s["t0"], 0.0))


def share(run, spans: List[dict]) -> float:
    """Percent of the window that ``spans`` cover (they do not overlap:
    one row, one span open at a time)."""
    return 100.0 * sum(clipped(run, s) for s in spans) / run["seconds"]


def _share_of(run, name: str) -> Optional[float]:
    row = queue_row(run)
    if row is None:
        return None
    return share(run, [s for s in row if s["name"] == name])


def device_starved(run) -> Optional[float]:
    """Percent of the window over which the engine knew the device's queue
    empty while the server had work."""
    return _share_of(run, STARVED)


def no_work(run) -> Optional[float]:
    """Percent of the window over which the engine knew the device's queue
    empty and the server had no request: the load, not the program."""
    return _share_of(run, NO_WORK)


def starved_after_first_token(run) -> Optional[float]:
    """The part of ``device_starved`` whose span begins where an engine-row
    ``first_token_wait`` ends: the first-token sync — the host waited for a
    final chunk's whole call with nothing queued behind it."""
    row = queue_row(run)
    if row is None:
        return None
    engine = S.engine_row(run)
    S.need(engine, FIRST_TOKEN_WAIT)
    ends = sorted(S.end(s) for s in engine if s["name"] == FIRST_TOKEN_WAIT)

    def after_first_token(s):
        i = bisect.bisect_left(ends, s["t0"] - _TOUCH_S)
        return i < len(ends) and ends[i] <= s["t0"] + _TOUCH_S

    return share(run, [s for s in row if s["name"] == STARVED
                       and after_first_token(s)])


def fused_chunk_share(run) -> Optional[float]:
    """Of the ``prefill_chunk`` spans begun inside the window, the percent
    that begin inside an engine-row ``decode_dispatch`` span: the chunk rode
    in the decode trip's program call, and the tick read the weights once.
    Every other chunk had a call of its own (inside the ``prefill`` phase,
    or after a tick that dispatched no trip). Reads spans that exist since
    PR 32; the counters ``serving_prefill_chunks_fused`` / ``_alone`` say
    the same of the whole run. None where the window holds no chunk."""
    engine = S.engine_row(run)
    if engine is None:
        return None
    S.need(engine, "decode_dispatch")
    start = S.window_start(run)
    chunks = [s["t0"] - start for s in run["spans"]
              if s["name"] == "prefill_chunk"]
    if not chunks:
        raise RuntimeError("an engine row but no prefill_chunk span in the "
                           "whole run: renamed or removed")
    chunks = [t for t in chunks if 0.0 <= t < run["seconds"]]
    if not chunks:
        return None
    disp = [s for s in engine if s["name"] == "decode_dispatch"]
    starts = [s["t0"] for s in disp]

    def rode(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t <= S.end(disp[i])

    return 100.0 * sum(rode(t) for t in chunks) / len(chunks)
