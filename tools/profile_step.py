"""Per-op device profile of one train-step config (VERDICT r5 item 2: the
S=16384 step has a 0.4185 MFU with no train-level accounting).

Traces N steps with jax.profiler, parses the Chrome trace the xplane
converter writes, and buckets device-op time into attention kernels /
lm-head+CE / optimizer updates / other fusions — so "is long-S bound by
the 9-plane attention kernel or by CE/scan overhead?" gets a measured
answer instead of an inference.

Usage: python tools/profile_step.py [--seq 16384 --batch 1]
       [--layers 8 --hidden 2048]    # 509M headline dims by default
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def bucket_of(name: str, args: dict) -> str:
    """Buckets keyed on the HLO metadata, not the mangled event name: the
    flash BACKWARD kernels surface as `transpose_jvp___*` (the autodiff
    transpose of the custom_vjp) with hlo_category=custom-call — name
    matching alone mislabels them as layout copies (r5 lesson)."""
    n = name.lower()
    tf_op = str(args.get("tf_op", "")).lower()
    cat = str(args.get("hlo_category", "")).lower()
    src_line = str(args.get("source", ""))
    if "pallas" in tf_op or "custom-call" in cat or "mosaic" in n:
        if "flash" in src_line or "llama.py" in src_line or "flash" in n:
            return "attention_kernels"
        return "custom_calls"
    if "fused_ce" in src_line or "log_softmax" in n or "take_along" in n:
        return "lmhead_ce"
    if "while" in n:
        return "loops(ce_chunks/stream)"
    if "optimizer" in src_line or "adam" in n:
        return "optimizer"
    if n and n[0].isdigit() or n.startswith("jit_"):
        return "_step_markers"  # parent regions, excluded from totals
    if "copy" in n or "transpose" in n:
        return "copy_transpose"
    if "fusion" in n or "dot" in n or "conv" in n:
        return "matmul_fusions"
    return "other"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--inter", type=int, default=5632)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--keep", default=None,
                    help="keep the trace dir at this path")
    ap.add_argument("--parse-only", default=None,
                    help="re-analyze an existing trace dir; no chip run")
    args = ap.parse_args()

    if args.parse_only:
        meta = {}
        mp = os.path.join(args.parse_only, "pt_profile_meta.json")
        if os.path.exists(mp):
            meta = json.load(open(mp))
        return analyze(args.parse_only, args,
                       ms=meta.get("step_ms", 0.0),
                       n_params=meta.get("n_params", 0),
                       steps_traced=meta.get("steps_traced",
                                             args.steps + 1))
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.utils.bench_timing import device_time_ms

    assert any(d.platform == "tpu" for d in jax.devices()), \
        "profile_step wants the real chip"
    cfg = LlamaConfig(vocab_size=32000, hidden_size=args.hidden,
                      intermediate_size=args.inter,
                      num_hidden_layers=args.layers,
                      num_attention_heads=args.hidden // 128,
                      num_key_value_heads=max(args.hidden // 256, 1),
                      max_position_embeddings=args.seq, dtype="bfloat16",
                      use_flash_attention=True)
    paddle.seed(0)
    trace_dir = args.keep or tempfile.mkdtemp(prefix="pt_trace_")
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters())
    engine = ParallelEngine(model, optimizer=opt, loss_fn=None,
                            remat=args.remat)
    engine.build_train_step()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size,
                    (args.batch, args.seq)).astype("int32"))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size,
                    (args.batch, args.seq)).astype("int64"))
    ms = device_time_ms(lambda: engine.train_batch(ids, labels),
                        reps=2, warmup=2)  # warms compile + cache
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        engine.train_batch(ids, labels)
    # force completion INSIDE the trace window
    float(np.asarray(engine.train_batch(ids, labels).value))
    jax.profiler.stop_trace()

    with open(os.path.join(trace_dir, "pt_profile_meta.json"), "w") as f:
        json.dump({"step_ms": ms, "n_params": n_params,
                   "steps_traced": args.steps + 1,
                   "config": vars(args)}, f)
    analyze(trace_dir, args, ms, n_params, args.steps + 1)
    if not args.keep:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)


def analyze(trace_dir, args, ms, n_params, steps_traced):
    traces = glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True)
    assert traces, f"no trace written under {trace_dir}"
    with gzip.open(sorted(traces)[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # device lanes: pick pids whose process names mention TPU/device
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events if e.get("ph") == "M"
                 and e.get("name") == "process_name"}
    dev_pids = {p for p, n in pid_names.items()
                if "tpu" in n.lower() or "device" in n.lower()
                or "/device" in n.lower()}
    if not dev_pids:  # fall back: everything that isn't python/host
        dev_pids = {p for p, n in pid_names.items()
                    if "python" not in n.lower() and "host" not in n.lower()}
    agg, buckets = {}, {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        name = e.get("name", "?")
        b = bucket_of(name, e.get("args", {}))
        dur = e.get("dur", 0) / 1e3  # ms
        a = agg.setdefault(name, [0, 0.0, b])
        a[0] += 1
        a[1] += dur
        if b == "_step_markers":
            continue  # parent spans would double-count their children
        buckets[b] = buckets.get(b, 0.0) + dur
        total += dur
    print(f"\n== device-op profile: {n_params/1e6:.0f}M, B={args.batch} "
          f"S={args.seq} remat={args.remat} ({steps_traced} steps traced, "
          f"step {ms:.1f} ms) ==")
    print(f"total device-op time {total:.1f} ms "
          f"({total / steps_traced:.1f} ms/step vs {ms:.1f} wall — "
          f"overlap if smaller)")
    print("\n-- buckets --")
    for b, t in sorted(buckets.items(), key=lambda kv: -kv[1]):
        print(f"  {b:<20} {t:>9.1f} ms  {100 * t / max(total, 1e-9):5.1f}%")
    print(f"\n-- top {args.top} ops --")
    for name, (calls, t, b) in sorted(agg.items(), key=lambda kv: -kv[1][1]
                                      )[:args.top]:
        print(f"  {t:>9.2f} ms  x{calls:<5} [{b:<16}] {name[:90]}")


if __name__ == "__main__":
    main()
