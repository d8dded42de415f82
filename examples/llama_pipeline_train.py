"""Compiled pipeline-parallel Llama training on a dp x pipe x tensor mesh.

Needs eight devices. Without eight chips, let virtual CPU devices stand in —
the platform is the caller's choice, never the script's:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/llama_pipeline_train.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import llama_pipeline_engine


def main():
    cfg = llama_tiny_config(use_flash_attention=False, num_hidden_layers=4)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())

    if jax.device_count() < 8:
        sys.exit(f"this example needs 8 devices, JAX found "
                 f"{jax.device_count()} ({jax.default_backend()}); see the "
                 f"module docstring for the virtual-CPU-device invocation")
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "pipe", "tensor"))
    eng = llama_pipeline_engine(model, optimizer=opt, mesh=mesh, num_micro=2)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (8, 64)).astype("int32"))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (8, 64)).astype("int64"))
    for step in range(5):
        loss = eng.train_batch(ids, labels)
        print(f"step {step}: loss {float(np.asarray(loss.value)):.4f}")
    eng.sync_to_model()  # weights back into the model for checkpointing
    paddle.save(model.state_dict(), "/tmp/llama_pp.pdparams")
    print("saved /tmp/llama_pp.pdparams")


if __name__ == "__main__":
    main()
