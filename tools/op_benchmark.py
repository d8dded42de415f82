"""Op-level benchmark harness (ref tools/ci_op_benchmark.sh + the op
benchmark CI it drives — relative perf gates on core ops).

Measures wall latency of a representative op set through the public API on
the current backend and writes JSON: {op: {"ms": ..., "shape": ...}}.
Pair with check_op_benchmark_result.py to gate regressions between runs:

    python tools/op_benchmark.py -o base.json        # on the base commit
    python tools/op_benchmark.py -o head.json        # on the candidate
    python tools/check_op_benchmark_result.py base.json head.json --tol 1.15
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _bench(fn, *args, warmup=2, iters=10):
    from paddle_tpu.utils.bench_timing import device_time_ms

    return device_time_ms(lambda: fn(*args), reps=iters, warmup=warmup)


def build_suite():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.RandomState(0)
    on_tpu = jax.default_backend() == "tpu"
    dt = "bfloat16" if on_tpu else "float32"
    n = 2048 if on_tpu else 256

    a = paddle.to_tensor(rng.randn(n, n).astype("float32")).astype(dt)
    b = paddle.to_tensor(rng.randn(n, n).astype("float32")).astype(dt)
    img = paddle.to_tensor(rng.randn(8, 64, 56, 56).astype("float32"))
    conv = paddle.nn.Conv2D(64, 128, 3, padding=1)
    x3 = paddle.to_tensor(rng.randn(32, n).astype("float32"))
    ln = paddle.nn.LayerNorm(n)
    emb_ids = paddle.to_tensor(rng.randint(0, 32000, (8, 512)).astype("int32"))
    emb = paddle.nn.Embedding(32000, 512)

    suite = {
        "matmul": (lambda: paddle.matmul(a, b), f"({n},{n})x({n},{n}) {dt}"),
        "conv2d_3x3": (lambda: conv(img), "(8,64,56,56)->128ch"),
        "softmax": (lambda: F.softmax(x3, axis=-1), f"(32,{n})"),
        "layer_norm": (lambda: ln(x3), f"(32,{n})"),
        "embedding": (lambda: emb(emb_ids), "(8,512) of 32000x512"),
        "reduce_sum": (lambda: paddle.sum(a, axis=-1), f"({n},{n})"),
    }
    if on_tpu:
        from paddle_tpu.ops.flash_attention import flash_attention

        q = jnp.asarray(rng.randn(4, 16, 2048, 128), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.randn(4, 8, 2048, 128), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.randn(4, 8, 2048, 128), dtype=jnp.bfloat16)
        fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
        suite["flash_attention_causal_gqa"] = (
            lambda: fa(q, k, v), "B4 H16/8 S2048 D128 bf16")
    return suite


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-o", "--output", default=None)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    from paddle_tpu.utils.bench_timing import UnstableMeasurement

    results = {}
    for name, (fn, shape) in build_suite().items():
        try:
            ms = _bench(fn, iters=args.iters)
        except UnstableMeasurement as e:  # below the timing noise floor
            print(f"{name:28s}   UNSTABLE   {shape}  ({e})")
            continue
        results[name] = {"ms": round(ms, 4), "shape": shape}
        print(f"{name:28s} {ms:9.3f} ms   {shape}")
    if args.output:
        with open(args.output, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
