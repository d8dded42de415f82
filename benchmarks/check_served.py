"""The comparison that decides ``correct`` for a served model.

Once the window has closed, ``memory_peak_bytes`` has been read and the
program's state is freed: take a sample, drawn from the seed, of the requests
the timed path finished, the longest among them; run the plain reference once
over each prompt with its served tokens (teacher-forced); and compare the
WIDEST gap by which a served token's reference logit lies below the
reference's best at that position. Valid for greedy tokens, which is all the
mixes send. Also compared, exactly: that every finished request kept its
prompt and got the number of tokens it asked for.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

import numpy as np


def sample_finished(run: dict, seed: int, k: int) -> List[int]:
    """Request indices: the longest finished one, then k-1 others drawn
    from the seed."""
    done = sorted(run["results"])
    if not done:
        return []
    longest = max(done, key=lambda i: (len(run["results"][i]), -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[j] for j in sorted(pick)]


def wrong_shape(run: dict) -> int:
    """Finished requests whose prompt was altered or whose token count is
    not what they asked for."""
    bad = 0
    ask = {r["idx"]: r["max_new_tokens"] for r in run["requests"]}
    for idx, seq in run["results"].items():
        p = run["prompts"][idx]
        if seq[:len(p)] != p or len(seq) != len(p) + ask[idx]:
            bad += 1
    return bad


def check(run: dict, limits: dict, seed: int, log=print,
          mode: str = "f32") -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Returns ``(correct, compared)``; ``compared`` maps each number's
    short name to ``{"value", "limit"}``."""
    ref = importlib.import_module(
        f"benchmarks.reference.{run['config']['reference']}")
    cfg, weights = run["config"], run["weights"]
    picks = sample_finished(run, seed, int(limits["sample_requests"]))
    worst, n_tok, n_argmax = 0.0, 0, 0
    for idx in picks:
        seq, p = run["results"][idx], run["prompts"][idx]
        gaps, _ = ref.served_gaps(weights, cfg, p, seq[len(p):],
                                  pad_to=int(limits["pad_to"]), mode=mode)
        if not np.isfinite(gaps).all():
            worst = float("inf")
        worst = max(worst, float(gaps.max()))
        n_tok += len(gaps)
        n_argmax += int((gaps == 0).sum())
    log(f"check: {len(picks)} finished requests, {n_tok} served tokens "
        f"against the float32 reference: {n_argmax} are its argmax")
    compared = {
        "logit_gap_max": {"value": worst if picks else float("inf"),
                          "limit": float(limits["logit_gap_max"])},
        "wrong_shape": {"value": float(wrong_shape(run)), "limit": 0.0},
        "served_tokens_checked": {"value": float(n_tok),
                                  "limit": float(limits["min_tokens"]),
                                  "at_least": True},
    }
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return ok, compared


def control_gap(run: dict, limits: dict, seed: int, mode: str) -> float:
    """The CONTROL: the reference in the nearest lower precision, put in
    the program's place. It need not decode: at each position of the same
    prompts and served tokens, the gap (in the float32 reference) of the
    token that the lower precision puts first. Returns the widest."""
    ref = importlib.import_module(
        f"benchmarks.reference.{run['config']['reference']}")
    cfg, weights = run["config"], run["weights"]
    worst = 0.0
    for idx in sample_finished(run, seed, int(limits["sample_requests"])):
        seq, p = run["results"][idx], run["prompts"][idx]
        served = seq[len(p):]
        _, lg = ref.served_gaps(weights, cfg, p, served,
                                pad_to=int(limits["pad_to"]))
        _, low = ref.served_gaps(weights, cfg, p, served,
                                 pad_to=int(limits["pad_to"]), mode=mode)
        first = low.argmax(-1)
        gaps = lg.max(-1) - lg[np.arange(len(first)), first]
        worst = max(worst, float(gaps.max()))
    return worst
