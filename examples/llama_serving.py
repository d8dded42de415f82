"""Continuous-batching generation serving.

    python examples/llama_serving.py                    # the default device
    JAX_PLATFORMS=cpu python examples/llama_serving.py  # explicitly on CPU
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference import GenerationServer
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config


def main():
    cfg = llama_tiny_config(use_flash_attention=False,
                            max_position_embeddings=256)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)

    srv = GenerationServer(model, max_batch=4, max_len=128,
                           prompt_buckets=(16, 32))
    rng = np.random.RandomState(0)
    rids = [srv.submit(rng.randint(1, cfg.vocab_size, (n,)).tolist(),
                       max_new_tokens=16)
            for n in (5, 11, 23, 8, 14, 30)]  # 6 requests through 4 slots
    results = srv.run()
    for rid in rids:
        print(f"request {rid}: {len(results[rid])} tokens ->",
              results[rid][-8:])


if __name__ == "__main__":
    main()
