"""MoE/EP performance existence: on-chip train row + dispatch-cost
breakdown (VERDICT r4 missing 2).

The reference ships fused MoE kernels + dedicated dispatch ops
(paddle/phi/kernels/fusion/moe_kernel.h, operators/collective/
global_scatter_op.cu). Our GShard dense-dispatch formulation (einsum over
one-hots, moe_layer.py) instead rides the MXU and lets GSPMD insert the
all_to_all. This tool measures, on one chip (expert axis degenerate):

- a 4-layer MoE-FFN train step (B=8, S=2048, d=1024, E=8, top-2):
  ms/step, tok/s, MFU over ACTIVE FLOPs (experts see E*C tokens);
- the step decomposed: gate+dispatch/combine einsums vs experts-only —
  the dense dispatch is O(T*E*C*d), so its share decides whether a fused
  (sorted-scatter) Pallas dispatch is worth building [go/no-go].

Usage: python tools/bench_moe.py [--d_model 1024] [--experts 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d_model", type=int, default=1024)
    ap.add_argument("--d_hidden", type=int, default=2816)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top_k", type=int, default=2)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.utils.bench_timing import device_time_ms, peak_flops

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    assert on_tpu, "MoE bench wants the real chip"

    D, H, E, K = args.d_model, args.d_hidden, args.experts, args.top_k
    B, S, L = args.batch, args.seq, args.layers
    T = B * S
    cf = 1.25
    C = max(int(cf * T * K / E), 1)

    class MoEStack(nn.Layer):
        def __init__(self):
            super().__init__()
            from paddle_tpu.nn import LayerList

            self.norms = LayerList([nn.LayerNorm(D) for _ in range(L)])
            self.moes = LayerList([
                MoELayer(d_model=D, num_experts=E, d_hidden=H, top_k=K)
                for _ in range(L)])
            self.head = nn.Linear(D, D)

        def forward(self, x):
            for norm, moe in zip(self.norms, self.moes):
                x = x + moe(norm(x))
            return self.head(x)

    paddle.seed(0)
    model = MoEStack()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(out, y):
        aux = sum((m.gate.loss for m in model.moes
                   if m.gate.loss is not None), 0.0)
        return paddle.mean((out - y) ** 2) + 0.01 * aux

    eng = ParallelEngine(model, optimizer=opt, loss_fn=loss_fn)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, S, D).astype("float32") * 0.1)
    y = paddle.to_tensor(rng.randn(B, S, D).astype("float32") * 0.1)
    step_ms = device_time_ms(lambda: eng.train_batch(x, y),
                             reps=5, warmup=2)
    loss = float(np.asarray(eng.train_batch(x, y).value))

    # ---- decomposition (forward-only, jitted pieces, same shapes) ----
    moe = model.moes[0]
    gate_w = jnp.asarray(moe.gate.weight.value)
    w1 = jnp.asarray(moe.experts.w1.value)
    w2 = jnp.asarray(moe.experts.w2.value)
    b1 = jnp.asarray(moe.experts.b1.value)
    b2 = jnp.asarray(moe.experts.b2.value)
    flat = jnp.asarray(rng.randn(T, D).astype("float32") * 0.1)
    buckets = jnp.asarray(rng.randn(E, C, D).astype("float32") * 0.1)

    # decomposition runs BOTH dispatch modes explicitly (the env is
    # read at trace time): full_moe in the current default mode plus a
    # forced-dense full pass, so dispatch_share always compares the
    # dense dispatch against the DENSE step it is part of
    cur_mode = os.environ.get("PT_MOE_DISPATCH", "sparse")

    def full_moe_fn(xv):
        out = moe(paddle.to_tensor(xv)).value
        return out.ravel()[0]

    full_moe = jax.jit(full_moe_fn)
    _ = full_moe(flat)  # trace in cur_mode
    os.environ["PT_MOE_DISPATCH"] = "dense"
    full_moe_dense = jax.jit(lambda xv: full_moe_fn(xv) + 0.0)
    _ = full_moe_dense(flat)  # trace in dense mode
    os.environ["PT_MOE_DISPATCH"] = cur_mode

    @jax.jit
    def experts_only(bk):
        out = moe.experts.run_experts(bk, w1, w2, b1, b2)
        return out.ravel()[0]

    @jax.jit
    def gate_dispatch_only(xv):
        topv, topi, aux = moe.gate.routing(xv, gate_w)
        onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)
        flat_oh = onehot.reshape(T * K, E)
        pos_in_e = jnp.cumsum(flat_oh, axis=0) - flat_oh
        pos = jnp.sum(pos_in_e * flat_oh, axis=-1).reshape(T, K)
        keep = pos < C
        oh_e = jax.nn.one_hot(topi, E, dtype=xv.dtype)
        oh_c = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=xv.dtype)
        dispatch = jnp.einsum("tke,tkc->tec", oh_e, oh_c)
        bk = jnp.einsum("tec,td->ecd", dispatch, xv)
        combine = jnp.einsum("tke,tkc,tk->tec", oh_e, oh_c,
                             topv.astype(xv.dtype))
        out = jnp.einsum("tec,ecd->td", combine, bk)
        return out.ravel()[0]

    moe_ms = device_time_ms(lambda: full_moe(flat), reps=5, warmup=2)
    moe_dense_ms = device_time_ms(lambda: full_moe_dense(flat), reps=5,
                                  warmup=2)
    exp_ms = device_time_ms(lambda: experts_only(buckets), reps=5,
                            warmup=2)
    disp_ms = device_time_ms(lambda: gate_dispatch_only(flat), reps=5,
                             warmup=2)

    tok_s = T / (step_ms / 1e3)
    # active FLOPs: experts compute on E*C token slots (fwd+bwd 3x). The
    # dense one-hot dispatch adds T*E*C*D einsums; the sparse path moves
    # the same tokens with gathers (no MXU FLOPs) — count dispatch FLOPs
    # only when they are actually executed
    expert_flops = 2 * E * C * (2 * D * H) * 3 * L
    dispatch_flops = (2 * (2 * T * E * C * D) * 3 * L
                      if os.environ.get("PT_MOE_DISPATCH",
                                        "sparse") == "dense" else 0)
    mfu = (expert_flops + dispatch_flops) / (step_ms / 1e3) / peak_flops()
    line = {
        "metric": "moe_train_tokens_per_sec_1chip",
        "value": round(tok_s, 1),
        "unit": f"tok/s ({L}L MoE-FFN d{D} E{E} top{K} C{C}, "
                f"{n_params/1e6:.0f}M params, loss={loss:.4f})",
        "ms_per_step": round(step_ms, 2),
        "dispatch_mode": os.environ.get("PT_MOE_DISPATCH", "sparse"),
        "mfu_active": round(mfu, 4),
        "decomp_ms": {"full_moe_fwd": round(moe_ms, 2),
                      "full_moe_fwd_dense": round(moe_dense_ms, 2),
                      "experts_only_fwd": round(exp_ms, 2),
                      "dense_gate_dispatch_combine_fwd": round(disp_ms, 2)},
        "dense_dispatch_share": (round(disp_ms / moe_dense_ms, 3)
                                 if moe_dense_ms else None),
    }
    print(json.dumps(line))


if __name__ == "__main__":
    main()
