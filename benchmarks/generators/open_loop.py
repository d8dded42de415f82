"""The one general open-loop traffic generator.

Reads a traffic file (``benchmarks/traffic/<name>.json``) and a seed and
returns the whole schedule before the run starts: when each request is due,
its prompt token ids and how many tokens it asks for. The program under test
never sees the seed, only the requests.

Every seed carries THE SAME WORK AT THE SAME TIMES. Requests come in blocks
of ``block`` requests; inside a block the prompt lengths, output lengths and
inter-arrival gaps are the stratified quantiles of their distributions (the
same multiset in every block), dealt to the block's requests in an order drawn
from the traffic file's ``order_seed`` and the block's number — so blocks
differ from each other, and no seed changes them. ``--seed`` draws the token
ids (and, in the driver, the weights). With the order left to the seed, runs
of one seed agreed to 1 % while seeds differed by 18 % in the TTFT tail of the
document mix (which long prompts happen to arrive together): the seed was
changing the work (my chip runs, PR 23). The pre-drawn timeline is copied in
spirit from tools/serving_benchmark.py; the stratification is new.

Traffic file keys::

    order_seed      fixes which request of a block gets which length and gap
    rate_rps        mean arrivals per second (fixed: the knee is swept once)
    knee_rps        the measured knee this rate was derived from (a record)
    block           requests per stratified block
    prompt / output {"dist": "lognormal", "median": m, "sigma": s,
                     "min": a, "max": b}
    ramp            {"seconds": r, "burst": n}: the schedule starts r seconds
                    BEFORE the window with n requests due at once, so the
                    window opens on a server in its steady state; the ramp is
                    set-up time
    drain_s         how long after the window the loop keeps serving so that
                    requests due inside it can finish
    unfinished_fails  true: a request due in the window and unfinished after
                    the drain is a failure (cells below the knee); false: it
                    is the queue an overloaded cell builds by design
    temperature     0.0 (greedy: what ``correct`` can check)
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    idx: int
    due: float            # seconds relative to the window's start (< 0: ramp)
    prompt: List[int]
    max_new_tokens: int


def _lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a clipped lognormal, as ints."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rate: float, n: int) -> np.ndarray:
    """The n stratified quantiles of Exp(rate), rescaled to mean 1/rate
    exactly (so a block always spans n/rate seconds)."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (n / rate) / g.sum()


def load_traffic(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def schedule(traffic: dict, vocab_size: int, seed: int, seconds: float,
             rate_rps: float | None = None) -> List[Request]:
    """All requests due in [-ramp, seconds + drain_s). ``rate_rps``
    overrides the file's rate (the knee sweep only)."""
    rate = float(rate_rps if rate_rps is not None else traffic["rate_rps"])
    block = int(traffic["block"])
    ramp = traffic.get("ramp", {})
    ramp_s = float(ramp.get("seconds", 0.0))
    burst = int(ramp.get("burst", 0))
    horizon = float(seconds) + float(traffic.get("drain_s", 0.0))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    order_seed = int(traffic["order_seed"])

    def dealt(block_no, *multisets):
        """The block's multisets, each in this block's own fixed order."""
        order = np.random.default_rng([order_seed, block_no])
        return [order.permutation(m) for m in multisets]

    p_len = _lengths(traffic["prompt"], block)
    o_len = _lengths(traffic["output"], block)
    gaps = _gaps(rate, block)
    reqs: List[Request] = []
    t = -ramp_s

    def add(due, pl, ol):
        prompt = rng.integers(1, vocab_size, size=int(pl)).tolist()
        reqs.append(Request(len(reqs), float(due), prompt, int(ol)))

    # the burst that fills the server at the start of the ramp: one more
    # draw from the same length multiset
    if burst:
        pp, oo = dealt(0, p_len, o_len)
        for i in range(burst):
            add(t, pp[i % block], oo[i % block])
    block_no = 0
    while t < horizon:
        block_no += 1
        pp, oo, gg = dealt(block_no, p_len, o_len, gaps)
        for i in range(block):
            t += gg[i]
            if t >= horizon:
                break
            add(t, pp[i], oo[i])
    return reqs
