"""Model-level benchmark harness (ref tools/ci_model_benchmark.sh — relative
model-perf gate). Runs a quick train-step benchmark for each flagship model
family and writes JSON {model: {"ms_per_step": ..., "tokens_or_imgs_per_s"}}.

Usage: python tools/model_benchmark.py [-o out.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def bench_llama():
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=2048, dtype="bfloat16",
                          use_flash_attention=True)
        B, S, iters = 8, 2048, 6
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256, dtype="float32",
                          use_flash_attention=False)
        B, S, iters = 2, 128, 3
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())
    engine = ParallelEngine(model, optimizer=opt, loss_fn=model.loss_fn,
                            remat=False)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype("int64"))
    from paddle_tpu.utils.bench_timing import device_time_ms

    ms = device_time_ms(lambda: engine.train_batch(ids, labels),
                        reps=iters, warmup=2)
    return {"ms_per_step": round(ms, 2),
            "tokens_per_s": round(B * S / (ms / 1e3), 1)}


def bench_llama_moe():
    """Mixtral-proxy train step (model-level MoE, r5): 8 SwiGLU experts
    top-2 in every FFN, sparse dispatch, aux loss in the LM objective.
    Active params/token ~= dense 509M-proxy's shape at E/K = 4x total."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.utils.bench_timing import device_time_ms, peak_flops

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # 8 experts of width 2816: ~700M total params (fits full AdamW
        # on 16 GB), ~330M active/token — the single-chip Mixtral proxy
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=2048, dtype="bfloat16",
                          use_flash_attention=True, moe_num_experts=8,
                          moe_top_k=2)
        B, S, iters = 4, 2048, 5
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256, dtype="float32",
                          use_flash_attention=False, moe_num_experts=4,
                          moe_top_k=2)
        B, S, iters = 2, 128, 3
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # active params/token: dense non-FFN + K/E of the expert stacks
    n_active = sum(
        int(np.prod(p.shape)) * (cfg.moe_top_k / cfg.moe_num_experts
                                 if ".moe.experts." in name else 1.0)
        for name, p in model.named_parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())
    engine = ParallelEngine(model, optimizer=opt, loss_fn=None, remat=False)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                           .astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                              .astype("int64"))
    ms = device_time_ms(lambda: engine.train_batch(ids, labels),
                        reps=iters, warmup=2)
    toks = B * S / (ms / 1e3)
    return {"ms_per_step": round(ms, 2),
            "tokens_per_s": round(toks, 1),
            "params_m": round(n_params / 1e6, 1),
            "active_params_m": round(n_active / 1e6, 1),
            "mfu_active_6nd": round(toks * 6.0 * n_active / peak_flops(), 4)}


def bench_resnet50():
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    on_tpu = jax.default_backend() == "tpu"
    B = 32 if on_tpu else 4
    model = resnet50(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                    parameters=model.parameters())
    from paddle_tpu.parallel import ParallelEngine

    def loss_fn(logits, labels):
        return paddle.nn.functional.cross_entropy(logits, labels)

    engine = ParallelEngine(model, optimizer=opt, loss_fn=loss_fn, remat=False)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, 3, 224, 224).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 10, (B,)).astype("int64"))
    from paddle_tpu.utils.bench_timing import device_time_ms

    ms = device_time_ms(lambda: engine.train_batch(x, y), reps=5, warmup=2)
    return {"ms_per_step": round(ms, 2),
            "imgs_per_s": round(B / (ms / 1e3), 1)}


def bench_ernie():
    """BASELINE config 2: ERNIE-3.0 base finetune (12L H768 A12, seq-cls,
    B=32 S=128 — the canonical PaddleNLP finetune recipe shape). MFU uses
    ~6·N·tokens like the llama bench (encoder fwd+bwd matmul estimate)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.ernie import (ErnieConfig,
                                         ErnieForSequenceClassification,
                                         ernie_tiny_config)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = ErnieConfig(vocab_size=40000, hidden_size=768,
                          num_hidden_layers=12, num_attention_heads=12,
                          intermediate_size=3072, hidden_dropout_prob=0.1,
                          attention_probs_dropout_prob=0.1,
                          max_position_embeddings=2048)
        B, S, iters = 32, 128, 8
    else:
        cfg = ernie_tiny_config()
        B, S, iters = 4, 32, 3
    model = ErnieForSequenceClassification(cfg, num_classes=2)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=5e-5, parameters=model.parameters())
    engine = ParallelEngine(model, optimizer=opt, loss_fn=model.loss_fn,
                            remat=False)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                           .astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, 2, (B,)).astype("int64"))
    from paddle_tpu.utils.bench_timing import device_time_ms, peak_flops

    ms = device_time_ms(lambda: engine.train_batch(ids, labels),
                        reps=iters, warmup=2)
    toks = B * S / (ms / 1e3)
    return {"ms_per_step": round(ms, 2),
            "tokens_per_s": round(toks, 1),
            "examples_per_s": round(B / (ms / 1e3), 1),
            "mfu_6nd": round(toks * 6.0 * n_params / peak_flops(), 4),
            "params_m": round(n_params / 1e6, 1)}


def bench_ocr_rec():
    """BASELINE config 5 (rec side): the CRNN+CTC recipe from
    examples/ocr_recognition.py — conv tower + BiLSTM + CTC, the actual
    PP-OCRv4-style rec training step, not a ResNet proxy."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.vision.models import CRNN, crnn_ctc_loss
    from paddle_tpu.nn import Layer

    class CRNNWithLoss(Layer):
        def __init__(self, rec):
            super().__init__()
            self.rec = rec

        def forward(self, imgs, labels, lengths):
            return crnn_ctc_loss(self.rec(imgs), labels, lengths)

    on_tpu = jax.default_backend() == "tpu"
    B, iters = (64, 8) if on_tpu else (8, 3)
    model = CRNNWithLoss(CRNN(num_classes=10, in_channels=1))
    opt = Adam(learning_rate=1e-3, parameters=model.parameters())
    engine = ParallelEngine(model, optimizer=opt, loss_fn=None, remat=False)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.rand(B, 1, 32, 96).astype("float32"))
    labels = paddle.to_tensor(rng.randint(1, 11, (B, 5)).astype("int32"))
    lengths = paddle.to_tensor(np.full((B,), 5, np.int32))
    from paddle_tpu.utils.bench_timing import device_time_ms

    ms = device_time_ms(lambda: engine.train_batch(imgs, labels, lengths),
                        reps=iters, warmup=2)
    return {"ms_per_step": round(ms, 2),
            "imgs_per_s": round(B / (ms / 1e3), 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-o", "--output", default=None)
    ap.add_argument("--models", default="llama,llama_moe,resnet50,ernie,ocr_rec")
    args = ap.parse_args()
    table = {"llama": bench_llama, "llama_moe": bench_llama_moe,
             "resnet50": bench_resnet50,
             "ernie": bench_ernie, "ocr_rec": bench_ocr_rec}
    results = {}
    for name in args.models.split(","):
        results[name] = table[name.strip()]()
        print(name, results[name])
    if args.output:
        with open(args.output, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
