"""Flash-attention kernel block-size sweep — run on a REAL TPU chip.

Times every (block_q, block_k) combination for each shape in its own
SUBPROCESS (the block size is baked into the compiled kernel, so
same-process env flips would silently reuse the first compilation) and
prints a ranked table plus the current-default comparison.

Usage (TPU):
    python tools/bench_flash_sweep.py [--shapes small|mid|long|mha|all] [--bwd]
"""
import argparse
import json
import os
import subprocess
import sys

SHAPES = {
    "small": [(8, 2048, 16, 8, 128)],          # the B=8 S=2048 GQA headline
    "mid": [(2, 8192, 16, 8, 128)],            # loop-kernel upper boundary
    "mha": [(8, 2048, 16, 16, 128)],           # KV=H (GPT-family attention)
    "long": [(1, 16384, 16, 8, 128)],          # S=16k streaming target
    "all": [(8, 2048, 16, 8, 128), (2, 8192, 16, 8, 128),
            (1, 16384, 16, 8, 128), (8, 2048, 16, 16, 128)],
}
BLOCKS = [(256, 256), (256, 512), (512, 256), (512, 512),
          (512, 1024), (1024, 512), (1024, 1024)]

_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax, jax.numpy as jnp
from paddle_tpu.ops.flash_attention import flash_attention

B, S, H, KV, D = %(shape)s
do_bwd = %(bwd)s
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D).astype("float32")).astype(jnp.bfloat16)
k = jnp.asarray(rng.randn(B, KV, S, D).astype("float32")).astype(jnp.bfloat16)
v = jnp.asarray(rng.randn(B, KV, S, D).astype("float32")).astype(jnp.bfloat16)

fwd = jax.jit(lambda a, b, c: flash_attention(a, b, c, True))
loss = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
    flash_attention(a, b, c, True).astype(jnp.float32)), argnums=(0, 1, 2)))

fn = loss if do_bwd else fwd
from paddle_tpu.utils.bench_timing import device_time_ms
# keep the differencing signal (reps x kernel time) well above host jitter,
# and take enough repeats that both chains hit their latency floor
reps = (60 if S <= 4096 else 16) if not do_bwd else (20 if S <= 4096 else 8)
ms = device_time_ms(lambda: fn(q, k, v), reps=reps, repeats=5)
# causal attention flops: ~0.5 * 4 * B*H*S^2*D fwd (x2.5 for fwd+bwd)
flops = 0.5 * 4.0 * B * H * S * S * D * (2.5 if do_bwd else 1.0)
print(json.dumps({"ms": ms, "tflops": flops / ms / 1e9}))
"""


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def run_config(shape, bq, bk, bwd):
    repo = _REPO
    env = dict(os.environ)
    env["PT_FLASH_BLOCK_Q"] = str(bq)
    env["PT_FLASH_BLOCK_K"] = str(bk)
    code = _CHILD % {"repo": repo, "shape": tuple(shape), "bwd": bwd}
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            return None
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        return None


def _peak_tflops():
    from paddle_tpu.utils.bench_timing import peak_flops

    return peak_flops() / 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="small", choices=list(SHAPES))
    ap.add_argument("--bwd", action="store_true",
                    help="time grad (fwd+bwd) instead of forward only")
    args = ap.parse_args()
    peak = _peak_tflops()

    winners = {}  # seq_len -> (bq, bk)
    for shape in SHAPES[args.shapes]:
        print(f"\n== shape B,S,H,KV,D = {shape} "
              f"({'fwd+bwd' if args.bwd else 'fwd'}) ==")
        rows = []
        for bq, bk in BLOCKS:
            r = run_config(shape, bq, bk, args.bwd)
            tag = f"bq={bq:4d} bk={bk:4d}"
            if r is None:
                print(f"  {tag}: FAILED/OOM")
                continue
            if r["tflops"] > peak:
                # physically impossible (> chip peak): the differencing
                # signal was below the host jitter — never let such a row
                # become the winner
                print(f"  {tag}: {r['ms']:7.3f} ms  {r['tflops']:6.1f} "
                      f"TFLOP/s  SUSPECT (> {peak:.0f} peak, excluded)")
                continue
            rows.append((r["ms"], bq, bk, tag, r["tflops"]))
            print(f"  {tag}: {r['ms']:7.3f} ms  {r['tflops']:6.1f} TFLOP/s")
        if rows:
            rows.sort()
            ms, bq, bk, tag, tflops = rows[0]
            print(f"  BEST: {tag} at {ms:.3f} ms ({tflops:.1f} TFLOP/s)")
            winners[shape[1]] = (bq, bk)
    if winners:
        # ready-to-adopt regime map for the PT_FLASH_BLOCKS(_BWD) env
        # override / ops/flash_attention._BLOCK_REGIMES_FWD/_BWD tables
        adopt = ",".join(f"{s}:{bq}x{bk}"
                         for s, (bq, bk) in sorted(winners.items()))
        var = "PT_FLASH_BLOCKS_BWD" if args.bwd else "PT_FLASH_BLOCKS"
        table = "_BLOCK_REGIMES_BWD" if args.bwd else "_BLOCK_REGIMES_FWD"
        print(f"\nADOPT: {var}=\"{adopt}\"  (or fold into {table})")
        if args.bwd:
            # this sweep forces ONE uniform block for both directions and
            # times fwd+bwd together, so a "bwd winner" can encode a
            # suboptimal bwd-only choice when the fwd kernel dominates
            print("NOTE: --bwd times fwd+bwd with a uniform block; confirm "
                  "close winners with tools/bench_flash_pairwise.py (which "
                  "varies fwd and bwd blocks independently) before folding "
                  "into _BLOCK_REGIMES_BWD")


if __name__ == "__main__":
    main()
