"""AOT-validate the Llama-3-70B 4D-hybrid training program (BASELINE config 4).

TRUE 4D: dp × ZeRO-sharding × tensor × PIPE over a 16-virtual-device mesh
(2×2×2×2), with the block stack pipelined through the compiled GPipe scan
(`parallel.PipelineEngine`) — ref fleet.py:385 `_init_hybrid_parallel_env`
(dp×pp×sharding×mp all at once). The full train step (fwd + bwd + AdamW) is
lowered with ABSTRACT engine params/opt-state (no 70B optimizer memory), but
the eager model build itself does materialize zero-filled fp32 host arrays:
~5.5GB/layer — default --layers 4 needs ~22GB host RAM; --layers 80 would
need a ~300GB host. With --compile the partitioned HLO must contain
collective-permute (pipe ppermute) alongside the TP all-reduce and ZeRO
all-gather sites.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
        python tools/validate_70b_4d.py [--layers N] [--seq 4096] [--compile]

--layers trims the depth (the sharding structure is per-layer identical, so
8 layers exercises the same program shapes ~10x faster; pass 80 for the
full model). Must stay divisible by the 2 pipeline stages.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N_DEV = 16  # 2 data × 2 sharding × 2 tensor × 2 pipe


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--compile", action="store_true",
                    help="run GSPMD partitioning too (slower) and report "
                         "collective counts in the partitioned HLO")
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_DEV}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    # pin the platform in-process before any backend query (conftest.py
    # pattern)
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama3_70b_config

    assert jax.device_count() >= N_DEV, \
        f"need {N_DEV} devices (run with XLA_FLAGS=" \
        f"--xla_force_host_platform_device_count={N_DEV})"
    devs = np.asarray(jax.devices()[:N_DEV]).reshape(2, 2, 2, 2)
    mesh = Mesh(devs, ("data", "sharding", "tensor", "pipe"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    # float32: XLA's CPU backend crashes in AllReducePromotion cloning a
    # bf16 all-reduce ("Invalid binary instruction opcode copy"); the
    # partitioning/collective structure being validated is dtype-independent
    cfg = llama3_70b_config(num_hidden_layers=args.layers,
                            max_position_embeddings=args.seq,
                            dtype="float32")
    t0 = time.time()
    paddle.seed(0)
    # zero-fill initializers: at 70B scale random init dominates build time
    # and the lowering never reads values — only shapes/dtypes matter here
    from paddle_tpu.nn import initializer as I

    def _zeros_init(self, shape, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)

    for cls in (I.Normal, I.Uniform, I.XavierNormal, I.XavierUniform,
                I.KaimingNormal, I.KaimingUniform, I.TruncatedNormal):
        cls.__call__ = _zeros_init
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    print(f"model built: {n_params/1e9:.2f}B params ({args.layers} layers) "
          f"in {time.time()-t0:.0f}s")

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import llama_pipeline_engine

    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())
    eng = llama_pipeline_engine(model, optimizer=opt, mesh=mesh,
                                num_micro=args.micro, remat=True,
                                abstract=True, fsdp=True)
    # stage-sharded + ZeRO: every stacked leaf carries pipe and most carry
    # the sharding axis too
    piped = [s for s in eng.stacked_specs.values() if "pipe" in tuple(s)]
    zeroed = [s for s in eng.stacked_specs.values()
              if "sharding" in tuple(s)]
    print(f"stacked specs: {len(piped)}/{len(eng.stacked_specs)} pipe-sharded,"
          f" {len(zeroed)} ZeRO-sharded (e.g. "
          f"{eng.stacked_specs['self_attn.q_proj.weight']})")
    assert len(piped) == len(eng.stacked_specs)
    assert len(zeroed) > 0, "ZeRO sharding axis missing from stacked specs"

    ids = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", None)))
    lbl = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int64,
                               sharding=NamedSharding(mesh, P("data", None)))

    t0 = time.time()
    lowered = eng.lower_train_step((ids,), (lbl,))
    txt = lowered.as_text()
    n_shard = txt.count("sdy.sharding") + txt.count("mhlo.sharding")
    print(f"lowered in {time.time()-t0:.0f}s; {len(txt) // 1024}kB StableHLO, "
          f"{n_shard} sharding annotations")
    assert n_shard > 0, "no sharding annotations in lowered program"
    if args.compile:
        t0 = time.time()
        compiled = lowered.compile()
        hlo = compiled.as_text()
        print(f"GSPMD-compiled in {time.time()-t0:.0f}s")
        counts = {coll: hlo.count(coll)
                  for coll in ("all-gather", "reduce-scatter", "all-reduce",
                               "collective-permute")}
        for coll, n in counts.items():
            print(f"  {coll}: {n} sites")
        assert counts["collective-permute"] > 0, \
            "pipeline ppermute missing from partitioned HLO"
        assert counts["all-reduce"] > 0
        assert counts["all-gather"] > 0, "ZeRO all-gathers missing"
    # staggered interleaved 1F1B over the same 4D mesh: the new schedule
    # must also lower at scale (loss-inside-pipe, traced chunk gather).
    # abstract=True only reads shapes/dtypes — reuse the SAME model/opt
    # (a second eager build would double peak host RAM and build time)
    if args.layers % 4 == 0:
        t0 = time.time()
        eng2 = llama_pipeline_engine(model, optimizer=opt, mesh=mesh,
                                     num_micro=args.micro, remat=True,
                                     abstract=True, fsdp=True,
                                     num_chunks=2, schedule="1f1b")
        txt2 = eng2.lower_train_step((ids,), (lbl,)).as_text()
        n_shard2 = txt2.count("sdy.sharding") + txt2.count("mhlo.sharding")
        print(f"1f1b-interleaved (C=2) lowered in {time.time()-t0:.0f}s; "
              f"{len(txt2) // 1024}kB StableHLO, {n_shard2} annotations")
        assert n_shard2 > 0, "no sharding annotations in 1f1b lowering"
    else:
        print(f"1f1b-interleaved (C=2) leg skipped: --layers {args.layers} "
              f"not divisible by 2 stages x 2 chunks")
    print("70B 4D-hybrid (dp×sharding×tensor×pipe) validation OK")


if __name__ == "__main__":
    main()
