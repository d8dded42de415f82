"""What the per-layer metric readers share. A reader is a file
``layer_metrics/<metric name>.py`` with ``read(run)``; it returns a number, or
None where it finds nothing to read (the harness then leaves the metric out).
A roofline reader that matches no trace event RAISES: a kernel that went off
the path must not read as 0%.
"""
from __future__ import annotations

from typing import List, Optional

from . import stats, trace_reduce
from .roofline import decoder_step, least_seconds


def window_steps(run) -> List[dict]:
    return [s for s in run["steps"] if 0.0 <= s["t1"] < run["seconds"]]


def traced_steps(run) -> List[dict]:
    if not run.get("traced_window"):
        return []
    a, b = run["traced_window"]
    return [s for s in run["steps"] if s["t0"] >= a and s["t1"] <= b + 1e-9]


def gen_late_p95_ms(run) -> Optional[float]:
    late = [(r["submit_t"] - r["due"]) * 1e3 for r in run["requests"]
            if r["submit_t"] is not None and 0 <= r["due"] < run["seconds"]]
    return stats.percentile(late, 95) if late else None


def tick_ms(run) -> Optional[float]:
    st = window_steps(run)
    if not st:
        return None
    return sum(s["t1"] - s["t0"] for s in st) / len(st) * 1e3


def batch_occupancy(run) -> Optional[float]:
    st = window_steps(run)
    if not st:
        return None
    return 100.0 * stats.mean(
        [s["slots_occupied"] / s["slots_total"] for s in st])


def queue_depth_min(run) -> Optional[float]:
    """Least ``load_metrics()["queue_depth"]`` over the window's steps: the
    room a capacity cell has left. 0 means a slot waited for work, and
    ``serve_tok_s`` is then no longer a capacity."""
    depths = [s["queue_depth"] for s in window_steps(run)
              if "queue_depth" in s]
    return float(min(depths)) if depths else None


def queue_wait_p95_ms(run) -> Optional[float]:
    waits = [s["dur"] * 1e3 for s in run.get("spans", [])
             if s["name"] == "queued"]
    return stats.percentile(waits, 95) if waits else None


def mfu(run) -> Optional[float]:
    """Required model FLOPs of the window's tokens over the window's whole
    length and the peak of the chips used."""
    st = window_steps(run)
    if not st:
        return None
    chunks = [c for s in st for c in s["prefill_chunks"]]
    flops = decoder_step.serve_flops(
        run["config"], chunks, sum(s["decode_rows"] for s in st),
        sum(s["decode_ctx"] for s in st), sum(s["tokens"] for s in st))
    return 100.0 * flops / run["seconds"] / (
        run["chips"] * run["peaks"]["bf16_flops"])


def device_idle(run) -> Optional[float]:
    dev = run.get("device")
    if not dev or "busy_s" not in dev:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def kernel_roofline(run, patterns, flops: float, nbytes: float) -> float:
    """Share of the roofline of the events matching ``patterns`` on the
    traced device: least time the chip could take over their device time."""
    ops = run.get("device_ops")
    if ops is None:
        raise RuntimeError("roofline reader called without a device trace")
    evs = trace_reduce.match(ops, patterns)
    if not evs:
        raise RuntimeError(
            f"no trace event matches {patterns}: the kernel left the path or "
            f"changed its name — the yardstick must not read 0")
    if flops <= 0:
        raise RuntimeError("events matched but no work was observed for them")
    least, bound = least_seconds(flops, nbytes, run["peaks"])
    run.setdefault("roofline_bounds", {})[patterns[0]] = bound
    return 100.0 * least / sum(e.dur for e in evs)
