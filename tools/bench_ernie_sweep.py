"""ERNIE-3.0 base shape sweep: is 0.254 MFU the shape or the framework?

VERDICT r4 weak item 3: the first on-chip ERNIE row (B=32 S=128, 0.254
MFU) was labelled "the shape's ceiling territory" without evidence. This
driver sweeps B ∈ {32,128,256} × S ∈ {128,512} under the drift-robust
round-robin discipline (configs interleave; ranking + per-config medians).
If MFU climbs with B·S the 0.254 was the finetune shape; if it plateaus,
the encoder path has framework overhead to find.

Usage: python tools/bench_ernie_sweep.py [--rounds 2] [--configs 32x128,...]
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
assert any(d.platform == "tpu" for d in jax.devices()), \
    "TPU required, backend is " + jax.devices()[0].platform
import paddle_tpu as paddle
from paddle_tpu.models.ernie import ErnieConfig, ErnieForSequenceClassification
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import ParallelEngine
from paddle_tpu.utils.bench_timing import device_time_ms, peak_flops

B, S = %(B)d, %(S)d
cfg = ErnieConfig(vocab_size=40000, hidden_size=768, num_hidden_layers=12,
                  num_attention_heads=12, intermediate_size=3072,
                  hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                  max_position_embeddings=2048)
model = ErnieForSequenceClassification(cfg, num_classes=2)
n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
opt = AdamW(learning_rate=5e-5, parameters=model.parameters())
engine = ParallelEngine(model, optimizer=opt, loss_fn=model.loss_fn,
                        remat=False)
engine.build_train_step()
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype("int32"))
labels = paddle.to_tensor(rng.randint(0, 2, (B,)).astype("int64"))
ms = device_time_ms(lambda: engine.train_batch(ids, labels), reps=6, warmup=2)
toks = B * S / (ms / 1e3)
print(json.dumps({"ms": round(ms, 2), "tok_s": round(toks, 1),
                  "ex_s": round(B / (ms / 1e3), 1),
                  "mfu": round(toks * 6.0 * n_params / peak_flops(), 4)}))
"""


def run_once(b, s):
    code = _CHILD % {"repo": _REPO, "B": b, "S": s}
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             env=dict(os.environ), capture_output=True,
                             text=True, timeout=900)
        if out.returncode != 0:
            sys.stderr.write((out.stderr or "")[-400:] + "\n")
            return None
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs",
                    default="32x128,128x128,256x128,32x512,128x512")
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()
    configs = [tuple(int(v) for v in c.split("x"))
               for c in args.configs.split(",")]
    results = {c: [] for c in configs}
    for r in range(args.rounds):
        for c in configs:
            res = run_once(*c)
            if res is None:
                print(f"  round {r}: B={c[0]:3d} S={c[1]:3d}: FAILED/OOM",
                      flush=True)
                continue
            results[c].append(res)
            print(f"  round {r}: B={c[0]:3d} S={c[1]:3d}: MFU {res['mfu']:.4f}"
                  f" ({res['ms']:.1f} ms, {res['tok_s']:.0f} tok/s,"
                  f" {res['ex_s']:.0f} ex/s)", flush=True)
    print("\n== medians (ERNIE-3.0 base, 118M) ==")
    for c, rs in sorted(results.items()):
        if not rs:
            print(f"  B={c[0]:3d} S={c[1]:3d}: no data")
            continue
        med = statistics.median(x["mfu"] for x in rs)
        tok = statistics.median(x["tok_s"] for x in rs)
        print(f"  B={c[0]:3d} S={c[1]:3d}: median MFU {med:.4f} "
              f"({tok:.0f} tok/s, n={len(rs)})")


if __name__ == "__main__":
    main()
