#!/usr/bin/env python
"""Cost-model-driven serving autotuner CLI (paddle_tpu.autotune).

Searches the engine-tier serving config space (block geometry, tick
window, speculation, KV quant, pool sizing, scheduler policy) against a
seeded workload, with the analytic paged-tick cost model pruning the
candidate pool between measured rungs. The search is deterministic per
``--seed``: same seed + same workload -> same trial sequence and a
byte-identical winning profile (minus the timestamp).

Outputs:

- ``--out PATH``      the winning TunedProfile JSON — feed it back with
                      ``GenerationServer(profile=PATH)`` or
                      ``serving_benchmark --profile PATH``
- ``--trials-out DIR``  one ``trial_NN.json`` per measured trial
                      (``"kind": "autotune_trial"``) —
                      ``tools/telemetry_dump.py`` tabulates N of them
- ``--json``          one machine-readable summary line on stdout

``--pin knob=value`` (repeatable) freezes a knob, shrinking the space:
``--pin draft_k=0`` tunes everything but speculation, ``--pin
kv_quant='"int8"'`` forces the int8 pool. Values parse as JSON first,
bare strings otherwise.

``--fake-clock`` swaps the wall clock for a deterministic counting
clock: every measurement (hence the whole search) becomes bit-exact —
CI determinism checks run this twice and byte-compare the profiles.

Usage: python -m tools.autotune --budget 8 --seed 0 --out tuned.json
       [--requests 16 --max-new 32 --slots 8] [--repeat-suffix]
       [--long-prompts] [--mixed-priority] [--arrival-rate R --burst B]
       [--pin knob=value ...] [--trials-out DIR] [--fake-clock] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _parse_pin(s: str):
    if "=" not in s:
        raise argparse.ArgumentTypeError(
            f"--pin wants knob=value, got {s!r}")
    name, raw = s.split("=", 1)
    try:
        val = json.loads(raw)
    except ValueError:
        val = raw            # bare string, e.g. --pin kv_quant=int8
    return name.strip(), val


class _CountingClock:
    """Deterministic stand-in for time.perf_counter: each call advances
    a fixed quantum, so measured durations count events, not seconds."""

    def __init__(self, quantum: float = 1e-4):
        self.t = 0.0
        self.quantum = quantum

    def __call__(self) -> float:
        self.t += self.quantum
        return self.t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[1],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--budget", type=int, default=8,
                    help="measured candidate trials (the default-config "
                         "reference trial is extra)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the candidate stream AND the workload "
                         "traffic")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="write the winning TunedProfile JSON here")
    ap.add_argument("--trials-out", metavar="DIR", default=None,
                    help="write every trial record as DIR/trial_NN.json")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8,
                    help="GenerationServer max_batch for every trial")
    ap.add_argument("--max-len", type=int, default=None,
                    help="serving horizon (default: fits the workload)")
    ap.add_argument("--long-prompts", action="store_true",
                    help="prompt ladder 64-512 instead of 16-128")
    ap.add_argument("--repeat-suffix", action="store_true",
                    help="motif-tiled prompts (the speculative showcase)")
    ap.add_argument("--mixed-priority", action="store_true",
                    help="round-robin priority classes + tenants")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    metavar="R", help="open-loop arrivals at R req/s")
    ap.add_argument("--burst", type=int, default=4,
                    help="requests per arrival clump in open-loop mode")
    ap.add_argument("--pin", action="append", type=_parse_pin, default=[],
                    metavar="KNOB=VALUE",
                    help="freeze a knob (repeatable); values parse as "
                         "JSON first, bare strings otherwise")
    ap.add_argument("--fake-clock", action="store_true",
                    help="deterministic counting clock instead of the "
                         "wall clock (CI determinism checks)")
    ap.add_argument("--json", action="store_true",
                    help="one machine-readable summary line on stdout")
    args = ap.parse_args(argv)
    if args.budget < 1:
        ap.error("--budget must be >= 1")

    import jax
    import numpy as np   # noqa: F401  (benchmark parity: seeded weights)

    import paddle_tpu as paddle
    from paddle_tpu.autotune import TrialRunner, autotune, engine_space
    from paddle_tpu.autotune.workload import (LONG_PROMPT_LADDER,
                                              SHORT_PROMPT_LADDER,
                                              WorkloadSpec)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    ladder = LONG_PROMPT_LADDER if args.long_prompts else SHORT_PROMPT_LADDER
    need = max(ladder) + args.max_new + 1
    max_len = args.max_len if args.max_len is not None else need

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=max_len,
                          dtype="bfloat16", use_flash_attention=True)
    else:
        # the serving_benchmark CPU stand-in: hidden 128 keeps the tick
        # matmul-bound so serving ratios measure the design, not dispatch
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=max_len,
                          dtype="float32", use_flash_attention=False)
    paddle.seed(0)   # fixed weights: --seed varies traffic, not the model
    model = LlamaForCausalLM(cfg)

    workload = WorkloadSpec(
        requests=args.requests, max_new=args.max_new,
        prompt_ladder=ladder, vocab_size=cfg.vocab_size,
        repeat_suffix=args.repeat_suffix,
        mixed_priority=args.mixed_priority,
        arrival_rate=args.arrival_rate, burst=args.burst, seed=args.seed)
    clock = _CountingClock() if args.fake_clock else None
    runner = TrialRunner(model, workload, max_batch=args.slots,
                         max_len=max_len, clock=clock)
    space = engine_space(max_len=max_len, pins=dict(args.pin))
    log = None if args.json else (
        lambda s: print(f"[autotune] {s}", file=sys.stderr))
    profile, trials = autotune(runner, budget=args.budget,
                               seed=args.seed, space=space, log=log)

    if args.out:
        d = os.path.dirname(args.out)
        if d:
            os.makedirs(d, exist_ok=True)
        # the timestamp is the one non-deterministic field; --fake-clock
        # runs leave it unset so byte-comparisons stay meaningful
        profile.save(args.out,
                     now=None if args.fake_clock else time.time())
    if args.trials_out:
        os.makedirs(args.trials_out, exist_ok=True)
        for t in trials:
            p = os.path.join(args.trials_out, f"trial_{t.index:02d}.json")
            with open(p, "w") as f:
                json.dump(t.to_dict(), f, sort_keys=True, indent=1)
                f.write("\n")

    line = {
        "metric": "autotune_winner_tok_s",
        "value": round(float(profile.metrics["tok_s"]), 1),
        "unit": f"generated tok/s ({args.requests} reqs, {args.slots} "
                f"slots, max_new={args.max_new}, budget={args.budget})",
        "baseline_tok_s": round(float(profile.baseline["tok_s"]), 1),
        "config_fingerprint": profile.config_fingerprint,
        "config": profile.config,
        "workload_signature": profile.workload_signature,
        "trials": profile.search["trials"],
        "rejected": len(profile.search["rejected"]),
        "plan": profile.search["plan"],
        "seed": args.seed,
        "budget": args.budget,
        "fake_clock": bool(args.fake_clock),
        "out": args.out,
    }
    print(json.dumps(line))
    if not args.json:
        print(f"[autotune] winner {profile.config_fingerprint} "
              f"{line['value']} tok/s (default {line['baseline_tok_s']}), "
              f"{line['trials']} trials, {line['rejected']} rejected"
              + (f", profile -> {args.out}" if args.out else ""),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
