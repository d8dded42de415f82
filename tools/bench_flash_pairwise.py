"""Round-robin paired comparison of flash block configs — drift-robust.

A chip's effective throughput can drift over minutes, so one-shot A/Bs
mis-rank configs.  This driver interleaves the candidate configs
round-robin (A B C A B C ...) so slow drift hits every config equally,
then ranks by per-config MEDIAN across rounds.  Each run is a subprocess
(block sizes bake into the compiled kernel); the parent never touches JAX,
so one process holds the chip at a time.

Usage:
    python tools/bench_flash_pairwise.py --shape 8,2048,16,8,128 \
        --configs 512x512:512x512,512x1024:512x512 [--rounds 3] [--fwd-only]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax, jax.numpy as jnp
from paddle_tpu.utils.bench_timing import device_time_ms
from paddle_tpu.ops.flash_attention import flash_attention

B, S, H, KV, D = %(shape)s
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D).astype("float32")).astype(jnp.bfloat16)
k = jnp.asarray(rng.randn(B, KV, S, D).astype("float32")).astype(jnp.bfloat16)
v = jnp.asarray(rng.randn(B, KV, S, D).astype("float32")).astype(jnp.bfloat16)
if %(fwd_only)s:
    fn = jax.jit(lambda a, b, c: flash_attention(a, b, c, True))
    reps = 60 if S <= 4096 else 16
else:
    fn = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, True).astype(jnp.float32)), argnums=(0, 1, 2)))
    reps = 20 if S <= 4096 else 8
ms = device_time_ms(lambda: fn(q, k, v), reps=reps, repeats=5)
print(json.dumps({"ms": ms}))
"""


if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def run_once(shape, fwd_blocks, bwd_blocks, fwd_only):
    env = dict(os.environ)
    env.pop("PT_FLASH_BLOCK_Q", None)
    env.pop("PT_FLASH_BLOCK_K", None)
    env["PT_FLASH_BLOCKS"] = f"{shape[1]}:{fwd_blocks}"
    env["PT_FLASH_BLOCKS_BWD"] = f"{shape[1]}:{bwd_blocks}"
    code = _CHILD % {"repo": _REPO, "shape": tuple(shape),
                     "fwd_only": fwd_only}
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            return None
        return json.loads(out.stdout.strip().splitlines()[-1])["ms"]
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,2048,16,8,128",
                    help="B,S,H,KV,D")
    ap.add_argument("--configs", required=True,
                    help="comma list of FWDBQxFWDBK:BWDBQxBWDBK entries")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--fwd-only", action="store_true")
    args = ap.parse_args()

    shape = tuple(int(x) for x in args.shape.split(","))
    configs = []
    for ent in args.configs.split(","):
        fwd_b, _, bwd_b = ent.partition(":")
        configs.append((fwd_b, bwd_b or fwd_b))

    samples = {c: [] for c in configs}
    for rnd in range(args.rounds):
        for c in configs:
            ms = run_once(shape, c[0], c[1], args.fwd_only)
            tag = f"fwd={c[0]} bwd={c[1]}"
            if ms is None:
                print(f"  round {rnd}: {tag}: FAILED")
                continue
            samples[c].append(ms)
            print(f"  round {rnd}: {tag}: {ms:7.3f} ms", flush=True)

    print("\n== medians ==")
    ranked = sorted((statistics.median(v), c) for c, v in samples.items() if v)
    for med, c in ranked:  # ascending; winner first
        spread = (max(samples[c]) - min(samples[c])) / med * 100
        print(f"  fwd={c[0]:9s} bwd={c[1]:9s}: median {med:7.3f} ms "
              f"(spread {spread:4.0f}%, n={len(samples[c])})")
    if ranked:
        med, c = ranked[0]
        print(f"WINNER: fwd={c[0]} bwd={c[1]} at {med:.3f} ms")


if __name__ == "__main__":
    main()
