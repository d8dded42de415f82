"""What the readers of the latent-attention + held-experts cell's per-layer
metrics share, beside ``readers.py``: the expert layer's counters as
``drivers/serve_latent_moe.py`` keeps them after every ``step()``
(``run["moe_steps"]``, one entry per entry of ``run["steps"]``), paired with
their step. A run of a program without those counters has no such record, or
one that is all zeros; every sum is then zero and the reader returns None."""
from __future__ import annotations

from typing import List, Tuple


def _pairs(run) -> List[Tuple[dict, dict]]:
    return list(zip(run["steps"], run.get("moe_steps") or []))


def window_pairs(run) -> List[Tuple[dict, dict]]:
    return [(s, m) for s, m in _pairs(run)
            if 0.0 <= s["t1"] < run["seconds"]]


def traced_pairs(run) -> List[Tuple[dict, dict]]:
    if not run.get("traced_window"):
        return []
    a, b = run["traced_window"]
    return [(s, m) for s, m in _pairs(run)
            if s["t0"] >= a and s["t1"] <= b + 1e-9]


def total(pairs, key: str) -> int:
    return sum(m[key] for _, m in pairs)
