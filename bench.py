"""Llama pretraining step on one TPU chip — one process, fails without a chip.

Prints ONE JSON line: {"metric", "value", "unit", "device"}.
Metric = MFU of a bf16 Llama train step (fwd+bwd+AdamW) on a 509M-param
proxy model (the largest no-remat config that fits one 16 GB v5e) — the unit
string labels the proxy honestly. ``BENCH_B`` / ``BENCH_S`` / ``BENCH_LAYERS``
resize it (S=8192 B=2 is the long-context row; 16 layers B=2 the ~0.9B row).

This is not the repo's benchmark (ROADMAP A0 builds that); it is the train
step's timing harness, kept because tools/bench_remat.py imports
``_measure``. It never falls back: no accelerator, or a device missing from
the peaks table, is an error and a non-zero exit.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
if _REPO_DIR not in sys.path:
    sys.path.insert(0, _REPO_DIR)


def _measure(cfg, B, S, steps, warmup, remat=False):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.utils.bench_timing import device_time_ms, peak_flops

    cfg.max_position_embeddings = max(cfg.max_position_embeddings, S)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())
    # flash fwd+bwd keep attention residuals at O(S·D) and the fused chunked
    # lm-head CE (ops/fused_ce.py) never materializes [B,S,V] logits;
    # loss_fn=None routes labels into forward() so the model returns the
    # fused loss directly
    engine = ParallelEngine(model, optimizer=opt, loss_fn=None,
                            remat=remat,
                            remat_policy=os.environ.get("BENCH_REMAT_POLICY",
                                                        "dots"))
    engine.build_train_step()

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype("int64"))

    # train steps serialize on-device through the donated param state;
    # dispatch-chain differencing closes each chain with a scalar pull
    step_ms = device_time_ms(lambda: engine.train_batch(ids, labels),
                             reps=steps, repeats=2, warmup=warmup)
    loss = engine.train_batch(ids, labels)

    tokens_per_sec = B * S / (step_ms / 1e3)
    flops_per_token = 6.0 * n_params  # fwd+bwd matmul FLOPs approximation
    mfu = tokens_per_sec * flops_per_token / peak_flops()
    return mfu, tokens_per_sec, n_params, float(np.asarray(loss.value))


def main() -> int:
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(f"bench.py needs a TPU; JAX found {dev.platform!r}. "
                         f"A CPU timing is not a device metric.\n")
        return 1
    enable_compile_cache()

    from paddle_tpu.models import LlamaConfig

    B = int(os.environ.get("BENCH_B", 8))
    S = int(os.environ.get("BENCH_S", 2048))
    layers = int(os.environ.get("BENCH_LAYERS", 8))
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                      num_hidden_layers=layers, num_attention_heads=16,
                      num_key_value_heads=8, max_position_embeddings=2048,
                      dtype="bfloat16", use_flash_attention=True)
    mfu, tokens_per_sec, n_params, loss = _measure(cfg, B, S, steps=10,
                                                   warmup=3)
    print(json.dumps({
        "metric": "llama_train_mfu_1chip",
        "value": round(mfu, 4),
        "unit": f"MFU, {n_params / 1e6:.0f}M-proxy model "
                f"(tokens/s={tokens_per_sec:.0f}, B={B}, S={S}, "
                f"loss={loss:.3f})",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
