"""What the readers of a hybrid model's per-layer metrics share, beside
``readers.py``: the by-kind record that ``drivers/serve_hybrid.py`` keeps
after every ``step()`` (``run["hybrid_steps"]``, one entry per entry of
``run["steps"]``), paired with its step. A run of a program without those
counters has no such record; every function then returns an empty list and
the reader returns None."""
from __future__ import annotations

from typing import List, Tuple


def _pairs(run) -> List[Tuple[dict, dict]]:
    return list(zip(run["steps"], run.get("hybrid_steps") or []))


def window_pairs(run) -> List[Tuple[dict, dict]]:
    return [(s, h) for s, h in _pairs(run)
            if 0.0 <= s["t1"] < run["seconds"]]


def traced_pairs(run) -> List[Tuple[dict, dict]]:
    if not run.get("traced_window"):
        return []
    a, b = run["traced_window"]
    return [(s, h) for s, h in _pairs(run)
            if s["t0"] >= a and s["t1"] <= b + 1e-9]
