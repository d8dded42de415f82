"""Remat-policy sweep on the large-model (886M) single-chip config.

VERDICT r3 item 4: the 886M config (largest honest AdamW fit) measured
0.573–0.598 MFU with the ``dots`` policy vs 0.675 at 509M; this driver
A/Bs the checkpoint policies (engine remat_policy values, anchored on the
checkpoint_name annotations in models/llama.py) under the drift-robust
round-robin discipline of bench_flash_pairwise: policies interleave so
slow chip drift hits each equally; ranking by per-policy median.

Usage: python tools/bench_remat.py [--policies dots,save_attn,...]
       [--rounds 2]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_CHILD = r"""
import json, sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax
# a CPU sample must never enter the per-policy medians
assert any(d.platform == "tpu" for d in jax.devices()), \
    "TPU required, backend is " + jax.devices()[0].platform
from bench import _measure
from paddle_tpu.models import LlamaConfig

cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                  num_hidden_layers=16, num_attention_heads=16,
                  num_key_value_heads=8, max_position_embeddings=2048,
                  dtype="bfloat16", use_flash_attention=True)
mfu, tps, n, loss = _measure(cfg, 2, 2048, 5, 2, remat=%(remat)s)
print(json.dumps({"mfu": mfu, "tok_s": tps, "loss": loss}))
"""


def run_once(policy):
    env = dict(os.environ)
    remat = policy != "none"
    env["BENCH_REMAT_POLICY"] = policy if remat else "dots"
    code = _CHILD % {"repo": _REPO, "remat": remat}
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        if out.returncode != 0:
            sys.stderr.write((out.stderr or "")[-400:] + "\n")
            return None
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies",
                    default="dots,save_attn,save_attn_mlp,save_qkv_attn,none")
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()
    policies = args.policies.split(",")
    results = {p: [] for p in policies}
    for r in range(args.rounds):
        for p in policies:
            res = run_once(p)
            if res is None:
                print(f"  round {r}: {p:14s}: FAILED/OOM")
                continue
            results[p].append(res)
            print(f"  round {r}: {p:14s}: MFU {res['mfu']:.4f} "
                  f"({res['tok_s']:.0f} tok/s, loss {res['loss']:.3f})")
    print("\n== medians (886M, B=2 S=2048) ==")
    ranked = []
    for p, rs in results.items():
        if not rs:
            print(f"  {p:14s}: no data")
            continue
        med = statistics.median(x["mfu"] for x in rs)
        ranked.append((med, p))
        print(f"  {p:14s}: median MFU {med:.4f} (n={len(rs)})")
    if ranked:
        ranked.sort(reverse=True)
        print(f"WINNER: {ranked[0][1]} at MFU {ranked[0][0]:.4f}")


if __name__ == "__main__":
    main()
